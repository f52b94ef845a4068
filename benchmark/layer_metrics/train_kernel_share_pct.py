"""Flash attention forward, dq and dk/dv over device busy time in the traced
train steps."""

from benchmark.readers import kernel_share as read  # noqa: F401
