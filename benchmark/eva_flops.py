"""Rows, operations and bytes of EVA attention over a windowed paged cache
and of a decode step of a configuration that has it, computed from shapes,
from the client's records and from the program's own counters
(`stats()["eva"]`): the arithmetic behind the `eva_*` per-layer metrics,
kept with the yardstick like `flops.py`, `moe_flops.py` and
`latent_flops.py`.

Counts are what the algorithm needs.  A lane whose context is n tokens
attends `rows(n)` rows a layer, not n: one summary row for every `chunk`
tokens of each closed window and the exact rows of the open one.  A row is
read once for scores and once for values: 2 x heads x head_dim x 2 FLOPs and
2 x heads x head_dim x 2 bytes (16,384 each for EvaByte), whatever the
kernel's block-diagonal query multiplies besides.
"""

from __future__ import annotations

from benchmark import flops, metrics
from benchmark.latent_flops import window


def rows(n: int, f: dict) -> int:
    """Rows a lane of `n` context tokens (the current one included) attends
    over: the summaries of the windows before the last token's, and that
    window's rows up to it (128 floor((n - 1) / 2048) + (n - 1) mod 2048 + 1
    for EvaByte; `PagedKVCache.rows_held`)."""
    if n < 1:
        return 0
    closed, last = divmod(n - 1, f["window_size"])
    return closed * (f["window_size"] // f["chunk_size"]) + last + 1


def live_rows(records, t: float, f: dict) -> int:
    """Rows the requests in flight at time `t` hold, from the client's own
    records: each one's prompt plus the output tokens it has received by
    then, through `rows`."""
    total = 0
    for r in records:
        times = r["token_times"]
        if not times or r.get("error") or not times[0] <= t <= times[-1]:
            continue
        total += rows(r["prompt_len"] + sum(x <= t for x in times), f)
    return total


def slice_rows(run: dict, instants: int = 16):
    """`live_rows` over the traced slice, or None without one: the mean at
    `instants` evenly spaced moments of it (`metrics.slice_mean`, the one
    rule of the paged kernels' readers)."""
    return metrics.slice_mean(
        run, lambda records, t: live_rows(records, t, run["fields"]),
        instants)


def decode_attention(total_rows: float, lanes: int, f: dict):
    """(FLOPs, bytes) of one layer's single-query attention of `lanes` lanes
    over `total_rows` rows in all."""
    return flops.paged_decode(total_rows, lanes, f["n_heads"],
                              f["d_model"] // f["n_heads"])


def counters(run: dict):
    """The window's deltas of `stats()["eva"]`, or None where the program
    has no such counters."""
    w = window(run, "eva")
    if w is None:
        return None
    return {k: w[0][k] - w[1][k]
            for k in ("decode_steps", "ctx_tokens", "rows_attended",
                      "compactions")}


def rows_per_step(run: dict):
    c = counters(run)
    if c is None or c["decode_steps"] <= 0:
        return None
    return c["rows_attended"] / c["decode_steps"]


def layer_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """One layer's matrices: q, k, v, o and the SwiGLU's three (the norms,
    mu and phi are kilobytes)."""
    d = f["d_model"]
    return itemsize * (4 * d * d + 3 * d * f["d_ff"])


def head_bytes(f: dict, itemsize: int = 2) -> int:
    """The head's columns for every prediction head."""
    return itemsize * f["d_model"] * f["num_pred_heads"] * f["vocab_size"]


def step_bytes(f: dict, total_rows: float, itemsize: int = 2) -> float:
    """What a T=1 step must read: every layer's matrices, the head, and
    the rows its lanes attend over in every layer."""
    row = 2 * f["n_heads"] * (f["d_model"] // f["n_heads"]) * itemsize
    return (f["n_layers"] * (layer_weight_bytes(f, itemsize)
                             + total_rows * row)
            + head_bytes(f, itemsize))
