"""The paged decode kernel of the T=1 step over device busy time in the traced
slice."""

from benchmark.readers import kernel_share as read  # noqa: F401
