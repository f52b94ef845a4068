"""Device trace time of the `flash_attention` kernels (forward, dq, dk/dv; they
share one name today) against the least time the chip could take for the
attention the traced steps need: `flops.flash_fwd` + `flops.flash_bwd` per
layer and step at the per-chip batch, the larger of FLOPs over peak and
bytes over bandwidth.  Recomputed forward passes count as time, not as
work."""

from benchmark.readers import flash_roofline as read  # noqa: F401
