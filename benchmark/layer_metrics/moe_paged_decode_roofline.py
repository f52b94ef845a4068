"""`paged_decode_roofline` for a model with other kernels beside it and a
number of K/V heads of its own: device trace time of the
`paged_decode_attention` kernel against the least time for single-query
attention over the context the live requests held during the traced slice
(`flops.paged_decode`; the context is the slice's mean of the client's own
records)."""

from benchmark import readers


def read(run: dict):
    return readers.paged_roofline(run, run["fields"]["n_kv_heads"])
