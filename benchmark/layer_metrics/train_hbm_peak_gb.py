"""Peak device memory of the train worker's fullest chip: state and batches plus
the step's scratch (its saved activations).  Memory freed buys batch."""

from benchmark.readers import hbm_peak_gb as read  # noqa: F401
