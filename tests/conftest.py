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

import gc  # noqa: E402

import pytest  # noqa: E402

MAPS = "/proc/self/maps"
MAP_LIMIT = "/proc/sys/vm/max_map_count"


def memory_maps():
    """(maps this process holds, the kernel's limit a process), or None
    where the two cannot be read (no /proc)."""
    try:
        with open(MAPS, "rb") as held, open(MAP_LIMIT) as limit:
            return sum(1 for _ in held), int(limit.read())
    except (OSError, ValueError):
        return None


def release_programs_past_half_the_map_limit():
    """Every CPU executable a process holds keeps some ten memory maps and
    jit's caches let none go; at `vm.max_map_count` (65,530) LLVM's next
    mmap fails and the worker dies in `backend_compile_and_load` (PERF.md
    section 7, "Found (PR 59)").  Past half the limit the process gives its
    programs back; whoever needs one compiles it again."""
    seen = memory_maps()
    if seen and 2 * seen[0] > seen[1]:
        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True)
def _a_worker_never_reaches_the_map_limit():
    yield
    release_programs_past_half_the_map_limit()


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
