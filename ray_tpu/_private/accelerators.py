"""The TPU chips of a host: counting them, binding workers to them, and
deciding who leases one.

A chip belongs to one process at a time (libtpu opens its device node
exclusively), so hostd — which never opens the TPU runtime itself — hands
each TPU-leased worker its own chips through the environment libtpu reads
at start-up.  Checked on a four-chip v5e host with libtpu 0.0.34: four
workers confined with `chip_env` run side by side, each seeing one device;
a second process on the same chip fails with "Device or resource busy".
"""

from __future__ import annotations

import glob
import os
from typing import Optional, Sequence


def count_local_chips() -> int:
    """Chips this host exposes, read off the device nodes (`/dev/accel<N>`
    up to v4, one vfio group `/dev/vfio/<N>` per chip from v5e on) without
    opening the TPU runtime.  The PCI bus is not a substitute: a machine
    handed one chip of a four-chip board still lists four functions."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return sum(os.path.basename(p).isdigit() for p in glob.glob("/dev/vfio/*"))


def leasable(n: int, host_chips: int) -> bool:
    """Whether one worker can be given `n` of a host's chips: a single chip
    (confined) or all of them (unconfined).  Two-chip bounds on a 2x2 v5e
    host failed without a message, so other counts are refused."""
    return n == 1 or n == host_chips


def chip_env(chips: Sequence[int], host_chips: int) -> dict:
    """Environment that confines libtpu in a new worker to `chips`, a
    subset of the host's `host_chips`.  Empty for a lease of every chip:
    the worker then sees the host as libtpu finds it."""
    if not leasable(len(chips), host_chips):
        raise ValueError(
            f"cannot confine a worker to chips {list(chips)} of "
            f"{host_chips}: only one chip or the whole host is supported")
    if len(chips) == host_chips:
        return {}
    return {"TPU_VISIBLE_CHIPS": str(chips[0]),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


class ChipAllocator:
    """Which chip indices of a host are free.  hostd binds chips to a worker
    process for its whole life, idle time in the pool included: libtpu
    keeps a chip open until the process exits."""

    def __init__(self, host_chips: int):
        self.host_chips = host_chips
        self._free = list(range(host_chips))

    def acquire(self, n: int) -> Optional[tuple]:
        """Take the `n` lowest free chips; None if fewer are free."""
        if n > len(self._free):
            return None
        chips, self._free = tuple(self._free[:n]), self._free[n:]
        return chips

    def release(self, chips: Sequence[int]) -> None:
        self._free = sorted(self._free + list(chips))


# ---------------------------------------------------------------------------
# Who leases a chip.  Worker-side facts come from the environment hostd
# hands every worker; cluster-side facts from what the nodes advertise.
# ---------------------------------------------------------------------------

NODE_CHIPS_ENV = "RAY_TPU_NODE_CHIPS"    # chips of the worker's node, if any
LEASED_CHIPS_ENV = "RAY_TPU_CHIPS"       # chips bound to this worker, "0,1"


def leased_chips() -> tuple:
    """Chip indices hostd bound to this worker process (empty outside a
    TPU-leased worker)."""
    raw = os.environ.get(LEASED_CHIPS_ENV, "")
    return tuple(int(c) for c in raw.split(",") if c)


def require_chip_lease(what: str) -> None:
    """Refuse to build `what` on the CPU inside a worker of a node that
    has chips: hostd pinned this worker to the CPU because its lease
    names no TPU, and a CPU engine there would answer, slowly, with no
    sign that the chip sat idle."""
    node_chips = os.environ.get(NODE_CHIPS_ENV)
    if node_chips and not leased_chips():
        raise RuntimeError(
            f"{what} needs a chip lease: this node advertises TPU: "
            f"{node_chips} but the worker was leased none, so it is pinned "
            f"to the CPU.  Create the actor or task with num_tpus=1.")


def chips_per_host() -> int:
    """Chips of one TPU node of the connected cluster (0 if none advertise
    TPU)."""
    import ray_tpu
    return int(max((n["Resources"].get("TPU", 0) for n in ray_tpu.nodes()
                    if n["Alive"]), default=0))


def default_num_tpus(cls, requested):
    """`num_tpus` for an actor of `cls`: what the caller asked for; else one
    chip for a class marked ``leases_chip`` (it builds an engine) when the
    cluster advertises TPU; else nothing."""
    if requested is not None or not getattr(cls, "leases_chip", False):
        return requested
    return 1 if chips_per_host() else None
