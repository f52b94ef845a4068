"""Operations and bytes of a decode step of a stack that keeps a WINDOW over
K and V heads in some layers and the whole context in others, beside
sigmoid-routed experts and a shared one (`trinity-mini`), computed from
shapes, from the program's own counters (`stats()["paged"]`: `rows_full`,
`rows_window`; `["moe"]`; `["layers"]`) and from the traced slice's kernel
calls: the arithmetic behind the `swa_*` per-layer metrics, kept with the
yardstick like `moe_flops.py` and `ssm_flops.py` (whose counts of
single-query attention and of a grouped multiply these are).

Counts are what the algorithm needs, whatever runs or tiles implement it: a
window layer's T=1 step reads the K and V rows of a lane's last
`sliding_window` positions (all of them while the lane holds no more), a
full layer's those of its whole context; an expert is its PUBLISHED three
matrices, read once a step where an assignment hit it and not at all where
none did.  The layers of each kind are the program's own count
(`stats()["layers"]`: `kv`, `window`, `experts`), so no reader knows the
pattern.
"""

from __future__ import annotations

from benchmark import flops, moe_flops, ssm_flops

WINDOW_KERNEL = "window_paged_decode_attention"
FULL_KERNEL = "paged_decode_attention"


def layers(run: dict):
    """Layers of each kind a step runs, as the program counts them: `full`
    and `window` attention layers, `experts` layers and `dense` ones (an
    attention layer whose feed-forward is no expert layer); or None where
    the program does not say."""
    n = (run.get("stats1") or {}).get("layers")
    if not n or not all(k in n for k in ("kv", "window", "experts")):
        return None
    return {"full": n["kv"] - n["window"], "window": n["window"],
            "experts": n["experts"], "dense": n["kv"] - n["experts"]}


def attention_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """q, the gate and the output projection over the query heads, k and v
    over the key/value heads, one layer."""
    return itemsize * f["d_model"] * f["head_dim"] * (
        3 * f["n_heads"] + 2 * f["n_kv_heads"])


def expert_layer_weight_bytes(f: dict, experts_hit: float,
                              itemsize: int = 2) -> float:
    """Three matrices of every expert hit and of the shared ones, and the
    router."""
    d = f["d_model"]
    return itemsize * d * (
        3 * f["d_expert"] * (experts_hit + f["n_shared_experts"])
        + f["n_routed_experts"])


def step_weight_bytes(f: dict, n: dict, experts_hit: float,
                      itemsize: int = 2) -> float:
    """What one step reads of its weights: every layer's attention, the
    dense layers' feed-forward, every expert layer's router, shared expert
    and experts hit, and the head."""
    d = f["d_model"]
    return ((n["full"] + n["window"]) * attention_weight_bytes(f, itemsize)
            + n["dense"] * itemsize * 3 * d * f["d_ff"]
            + n["experts"] * expert_layer_weight_bytes(f, experts_hit,
                                                       itemsize)
            + itemsize * d * f["vocab_size"])


def row_bytes(f: dict, itemsize: int = 2) -> int:
    """A token's K and V rows in one layer."""
    return itemsize * 2 * f["n_kv_heads"] * f["head_dim"]


def rows_per_step(run: dict):
    """The window's averages per T=1 step, all lanes together: (context
    tokens, rows ONE full layer read, rows ONE window layer read), from
    `stats()["paged"]` at the window's two ends; None where the program
    does not count them."""
    s0 = (run.get("stats0") or {}).get("paged")
    s1 = (run.get("stats1") or {}).get("paged")
    n = layers(run)
    if not s0 or not s1 or not n or "rows_window" not in s1 \
            or "rows_window" not in s0:
        return None
    steps = s1["decode_steps"] - s0["decode_steps"]
    if steps <= 0:
        return None

    def per(key, over):
        return (s1[key] - s0[key]) / steps / over if over else 0.0

    return (per("ctx_tokens", 1), per("rows_full", n["full"]),
            per("rows_window", n["window"]))


def decode_steps(run: dict):
    """T=1 steps of the traced slice: the windowed kernel runs once a window
    layer of each."""
    n = layers(run)
    kernel = ssm_flops.kernel(run, WINDOW_KERNEL)
    if not n or not n["window"] or not kernel:
        return None
    return kernel["calls"] / n["window"]


def attention_s(rows: float, lanes: float, f: dict, peaks: dict) -> float:
    """Least time of one layer's single-query attention of `lanes` lanes
    over `rows` cached rows in all (`ssm_flops.paged_decode`: the query
    heads' operations, the key/value heads' bytes)."""
    return flops.roofline_s(*ssm_flops.paged_decode(rows, lanes, f),
                            peaks)[0]


def step_bytes(run: dict):
    """The bytes the traced slice's steps must move, or None where an input
    is missing: per step (counted from the grouped multiply's calls: three
    an expert layer) the weights, per T=1 step the K and V rows of the
    slice's own context in every full layer and of the lanes' windows in
    every window layer."""
    f, n = run["fields"], layers(run)
    grouped = ssm_flops.kernel(run, "moe_grouped_matmul")
    load = moe_flops.window_load(run)
    per = rows_per_step(run)
    t1 = decode_steps(run)
    context = ssm_flops.slice_context(run)
    if not n or not grouped or load is None or per is None or t1 is None \
            or context is None:
        return None
    _, _, hit, pairs = load
    steps = grouped["calls"] / 3 / n["experts"]
    # the slice's own context where the window's average moved from it: the
    # windows' rows scale with it only while lanes are shorter than one
    window_rows = per[2] * min(1.0, context / per[0]) if per[0] else 0.0
    return (steps * step_weight_bytes(f, n, hit / pairs)
            + t1 * row_bytes(f) * (n["full"] * context
                                   + n["window"] * window_rows))
