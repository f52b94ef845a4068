"""Device trace time of the `paged_decode_attention` kernel against the least
time for single-query attention over the context the live requests held
during the traced slice (`flops.paged_decode`; the context is the slice's
mean of the client's own records: each live request's prompt plus the tokens
it had by then).  Every head has its own cached keys and values here."""

from benchmark import readers


def read(run: dict):
    return readers.paged_roofline(run, run["fields"]["n_heads"])
