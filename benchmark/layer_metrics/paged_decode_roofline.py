"""Device trace time of the paged decode kernel (today it has no name of its
own: every `tpu_custom_call` of a serve trace is it) against the least time
for single-query attention over the context the live requests held during
the traced slice (`flops.paged_decode`; the context comes from the client's
own records: each live request's prompt plus the tokens it had by then)."""

from benchmark.readers import paged_roofline as read  # noqa: F401
