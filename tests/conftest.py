"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's analogue is the
in-process multi-node Cluster fixture, python/ray/cluster_utils.py:99): JAX on
CPU with xla_force_host_platform_device_count=8 stands in for an 8-chip TPU
slice, so every sharding/collective path is exercised without TPU hardware.
"""

import os
import sys

# Must be set before jax is imported anywhere.  Force cpu even if the outer
# environment selects a TPU platform — tests exercise shardings on the
# virtual mesh; real-chip runs go through chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    # Learning-regression gates (minutes each on a small host) carry
    # @pytest.mark.slow; `-m "not slow"` is the fast iteration suite,
    # a plain `pytest tests/` still runs everything (reference: test
    # size tags, SURVEY §4).
    config.addinivalue_line(
        "markers", "slow: long learning-gate tests (deselect with "
        "-m 'not slow')")
    config.addinivalue_line(
        "markers", "examples: executes the committed examples/ scripts "
        "as subprocesses (select with -m examples)")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection scenarios "
        "(tests/test_fault_tolerance.py); fast cases run in tier-1, "
        "long soaks also carry `slow`")


@pytest.fixture
def tmp_store(tmp_path):
    from ray_tpu._private.object_store import ObjectStore

    store = ObjectStore.create(str(tmp_path / "store.shm"), 16 << 20)
    yield store
    store.close()
