"""Operations and bytes of a state-space mixer's one-token update and of its
chunked scan, of grouped-query paged attention beside it, and of a decode
step of a configuration that has both in every block (`falcon-h1-34b`),
computed from shapes, from the program's own counters (`stats()["ssm"]`,
`stats()["paged"]`, `stats()["prefill"]`) and from its `engine/step`
records of the traced slice: the arithmetic behind the `ssm_*` per-layer
metrics, kept
with the yardstick like `latent_flops.py` and `sparse_flops.py`.

Counts are what the algorithm needs, whatever implements it.  A lane's
state is heads x head_dim x d_state float32 numbers a layer; one token
reads and writes each once (decay, one multiply-add of the outer product,
one multiply-add against C: 5 operations a number).  A chunk of L tokens
of a scan costs, a group, C B^T (2 L L N) and, a head, the chunk's own
part (2 L L P), the carried state's part (2 L N P) and the state's update
(2 L N P); a row's state is read and written once a scan, whatever its
length.  Single-query attention reads each cached key and value of its
4 key/value heads once for all 20 query heads.
"""

from __future__ import annotations

from benchmark import metrics
from benchmark.latent_flops import window  # noqa: F401


def state_numbers(f: dict) -> int:
    """Numbers of one lane's recurrent state, one layer."""
    return f["ssm_heads"] * f["ssm_head_dim"] * f["ssm_state"]


def conv_width(f: dict) -> int:
    return f["ssm_heads"] * f["ssm_head_dim"] \
        + 2 * f["ssm_groups"] * f["ssm_state"]


def update(lanes: float, f: dict):
    """One layer's one-token update of `lanes` lanes: (flops, bytes).  The
    state float32, read and written; x, y (heads x head_dim), B, C (groups x
    d_state) and dt a lane in 2-byte numbers beside it."""
    n = state_numbers(f)
    small = 2 * f["ssm_heads"] * f["ssm_head_dim"] \
        + 2 * f["ssm_groups"] * f["ssm_state"] + f["ssm_heads"]
    return 5.0 * lanes * n, lanes * (2 * 4 * n + 2 * small)


def scan(tokens: float, rows: float, f: dict):
    """One layer's chunked scan of `tokens` tokens in all over `rows` rows
    (lanes that prefill): (flops, bytes)."""
    chunk, h, p = f["ssm_chunk"], f["ssm_heads"], f["ssm_head_dim"]
    g, n = f["ssm_groups"], f["ssm_state"]
    per_token = 2.0 * (g * chunk * n + h * (chunk * p + 2 * n * p))
    small = 2 * h * p + 2 * g * n + h
    return tokens * per_token, \
        rows * 2 * 4 * state_numbers(f) + tokens * 2 * small


def paged_decode(context_tokens: float, lanes: float, f: dict,
                 itemsize: int = 2):
    """One layer's single-query attention of `lanes` lanes over
    `context_tokens` cached tokens in all: q.K and p.V a cached token and
    QUERY head; each cached key and value of the key/value heads read once,
    q and o once a lane."""
    dh = f["head_dim"]
    flops = 2 * 2.0 * context_tokens * f["n_heads"] * dh
    nbytes = itemsize * dh * (2 * context_tokens * f["n_kv_heads"]
                              + 2 * lanes * f["n_heads"])
    return flops, nbytes


def layer_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """Attention's four matrices, the mixer's two with its convolution, the
    feed-forward's three."""
    d, dh = f["d_model"], f["head_dim"]
    d_ssm = f["ssm_heads"] * f["ssm_head_dim"]
    attn = d * dh * (2 * f["n_heads"] + 2 * f["n_kv_heads"])
    mixer = d * (d_ssm + conv_width(f) + f["ssm_heads"]) + d_ssm * d \
        + (f["ssm_conv"] + 1) * conv_width(f)
    return itemsize * (attn + mixer + 3 * d * f["d_ff"])


def step_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """What one step reads of its weights: every layer and the head."""
    return f["n_layers"] * layer_weight_bytes(f, itemsize) \
        + itemsize * f["d_model"] * f["vocab_size"]


def kv_bytes(f: dict, ctx_tokens: float, itemsize: int = 2) -> float:
    """The K and V rows of `ctx_tokens` tokens, every layer."""
    return itemsize * f["n_layers"] * ctx_tokens * 2 * f["n_kv_heads"] \
        * f["head_dim"]


def kernel(run: dict, name: str):
    """{calls, seconds} of the Mosaic kernel `name` in the traced slice."""
    k = ((run.get("trace") or {}).get("kernels") or {}).get(name)
    return k if k and k["seconds"] else None


def delta(run: dict, key: str, name: str):
    """stats1[key][name] - stats0[key][name], or None."""
    w = window(run, key)
    if w is None or name not in w[0] or name not in w[1]:
        return None
    return w[0][name] - w[1][name]


def lanes_per_update(run: dict):
    """Lanes a T=1 step stepped, the window's average."""
    tokens = delta(run, "ssm", "tokens_updated")
    steps = delta(run, "paged", "decode_steps")
    return tokens / steps if tokens and steps else None


def per_prefill_step(run: dict):
    """(tokens scanned, lanes that prefilled) a T>1 step, the window's
    averages."""
    tokens = delta(run, "ssm", "tokens_scanned")
    steps = delta(run, "prefill", "steps")
    lanes = delta(run, "prefill", "lanes")
    if not tokens or not steps or not lanes:
        return None
    return tokens / steps, lanes / steps


def slice_context(run: dict):
    """Context tokens a T=1 step of the traced slice attended over: the
    mean of `decode_ctx` over the program's own `engine/step` records of
    the slice's decoding iterations (what the paged kernel was handed,
    call by call).  Not the client's records of the accepted cells'
    reader (`metrics.slice_context_tokens`), which is only what is left
    where no record of the slice carries the count: a client learns of a
    token, and of a request's end, after the engine, and where its
    streams lag it holds requests live that have left their lanes (this
    cell's first check read 107% of the roofline so: PERF.md section 6)."""
    marks, trace = run.get("marks", {}), run["traffic"].get("trace", {})
    if "trace_on" in marks and "trace_off" in marks and "at_s" in trace:
        # The session is opened at base + at_s (the mark is set when the
        # start has returned) and closed slice_s later.
        on = min(marks["trace_on"], run["base"] + trace["at_s"])
        off = run["base"] + trace["at_s"] + float(trace["slice_s"])
        seen = [e["payload"]["decode_ctx"]
                for e in run.get("engine_events", [])
                if e["kind"] == "step" and e["payload"].get("decode_ctx")
                and on <= e.get("ts_adj", e["ts"]) <= off]
        if seen:
            return sum(seen) / len(seen)
    return metrics.slice_context_tokens(run) or None
