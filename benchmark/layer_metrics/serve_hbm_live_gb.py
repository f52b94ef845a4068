"""Live bytes on the replica's chip at the window's end: weights and the KV pool,
the step's scratch left out.  This, not the peak, says whether the cell fills
the chip as a deployment would."""

from benchmark.readers import hbm_live_gb as read  # noqa: F401
