"""Peak device memory of the replica's chip: weights and pool plus the step's
scratch.  Memory freed buys lanes and pool."""

from benchmark.readers import hbm_peak_gb as read  # noqa: F401
