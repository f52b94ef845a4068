"""Operations and bytes of a gated short convolution's one-token call and of
a decode step of a stack that has one in most layers beside a few attention
layers, over sigmoid-routed experts (`lfm2-24b-a2b`), computed from shapes,
from the program's own counters (`stats()["layers"]`, `["conv"]`, `["moe"]`)
and from the traced slice's kernel calls: the arithmetic behind the `conv_*`
per-layer metrics, kept with the yardstick like `ssm_flops.py` and
`moe_flops.py` (whose counts of single-query attention and of a grouped
multiply these are).

Counts are what the algorithm needs, whatever implements it.  A conv
mixer's call reads its weights once (W_in d x 3d, W_out d x d and the taps
k x d: 33.6 MB a layer at d = 2,048), each lane's tail (k - 1 rows of d
numbers) once and writes it once, and its activations once (`conv_mix`).
The per-lane part between the two products (the gate B * u, the taps, the
tail's overwrite, the gate C) has NO roofline here: the compiled step holds
the tails' whole buffer, the projection and the result in the chip's vector
memory (`S(1)` on every operand in the compiled text and in the trace's own
event names), so the part moves no byte through HBM, and `peaks.json` has
no peak for what it does move (PERF.md sections 6 and 7, PR 54: by the
4.2 MB a call that the algorithm moves it read 195% of the HBM roofline).
An expert that took no assignment is not read; a layer's K and V rows are
read once for all query heads.  The layers of each kind are the program's
own count (`stats()["layers"]`: `state` the conv layers, `kv` the attention
layers, `experts`; the dense feed-forward layers are the rest), never
`n_layers`.
"""

from __future__ import annotations

from benchmark import moe_flops, ssm_flops


def layers(run: dict):
    """Layers of each kind a step runs (`state`: conv mixers, `kv`:
    attention, `experts`), as the program counts them, or None where it
    does not."""
    n = (run.get("stats1") or {}).get("layers")
    return n if n and all(k in n for k in ("state", "kv", "experts")) \
        else None


def conv_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """A conv mixer's two projections and its taps."""
    d = f["d_model"]
    return itemsize * (4 * d * d + f["conv_taps"] * d)


def conv_mix(lanes: float, f: dict, itemsize: int = 2):
    """One layer's whole T=1 call over `lanes` lanes: the two products and
    the per-lane part between them ((2 k + 2) d operations a lane: the gate
    B * u, k taps, the gate C), the weights once, the lanes' tails read and
    written (k - 1 rows of d each way), the activations once (the normed
    input, the projection's three thirds written and read, the result)."""
    d, k = f["d_model"], f["conv_taps"]
    return (lanes * d * (2.0 * 4 * d + 2.0 * k + 2.0),
            conv_weight_bytes(f, itemsize)
            + itemsize * lanes * d * (2 * (k - 1) + 1 + 3 + 1 + 1))


def attention_weight_bytes(f: dict, itemsize: int = 2) -> int:
    return itemsize * f["d_model"] * f["head_dim"] * (
        2 * f["n_heads"] + 2 * f["n_kv_heads"])


def step_weight_bytes(f: dict, n: dict, experts_hit: float,
                      itemsize: int = 2) -> float:
    """What one step reads of its weights: every conv and attention
    operator, three matrices of every expert hit and the router in the
    expert layers, the dense SwiGLU in the others, and the tied head."""
    d = f["d_model"]
    dense = n["state"] + n["kv"] - n["experts"]
    return (n["state"] * conv_weight_bytes(f, itemsize)
            + n["kv"] * attention_weight_bytes(f, itemsize)
            + itemsize * (n["experts"] * d * (
                3 * experts_hit * f["d_expert"] + f["n_experts"])
                + dense * 3 * d * f["d_ff"] + d * f["vocab_size"]))


def kv_bytes(f: dict, n: dict, ctx_tokens: float, itemsize: int = 2) -> float:
    """The K and V rows of `ctx_tokens` tokens, every attention layer."""
    return itemsize * n["kv"] * ctx_tokens * 2 * f["n_kv_heads"] \
        * f["head_dim"]


def tail_bytes(f: dict, n: dict, lanes: float, itemsize: int = 2) -> float:
    """The tails of `lanes` lanes read and written, every conv layer."""
    return itemsize * n["state"] * lanes * 2 * (f["conv_taps"] - 1) \
        * f["d_model"]


def lanes_per_step(run: dict):
    """Lanes a T=1 population held a token in, the window's average
    (`stats()["conv"]`: `rows_t1` over `steps_t1`)."""
    rows = ssm_flops.delta(run, "conv", "rows_t1")
    steps = ssm_flops.delta(run, "conv", "steps_t1")
    return rows / steps if rows and steps else None


def steps(run: dict):
    """Step programs of the traced slice, from its kernel calls: every
    program (the T=1 step and the pair's) runs `paged_decode_attention`
    once an attention layer over its [max_lanes, 1] rows."""
    n = (layers(run) or {}).get("kv")
    kernel = ssm_flops.kernel(run, "paged_decode_attention")
    return kernel["calls"] / n if kernel and n else None


def step_bytes(run: dict):
    """The bytes the traced slice's steps must move, or None where an input
    is missing: per step the weights by the experts hit (the window's
    average a (layer, step) pair), the K and V rows of the attention layers
    at the context the slice's own steps attended over, and the tails of
    the lanes it stepped in every conv layer.  A chunk's own reads beside
    them (its rows' attention over their lanes' context) are left out: a
    lower count."""
    f, n = run["fields"], layers(run)
    count = steps(run)
    load = moe_flops.window_load(run)
    lanes = lanes_per_step(run)
    context = ssm_flops.slice_context(run)
    if not n or count is None or load is None or lanes is None \
            or context is None:
        return None
    _, _, hit, pairs = load
    return count * (step_weight_bytes(f, n, hit / pairs)
                    + kv_bytes(f, n, context) + tail_bytes(f, n, lanes))


def grouped_matmul_least_s(run: dict, peaks: dict):
    """Least seconds of the grouped multiplies of the traced slice, ONE
    population on both sides: every `moe_grouped_matmul` call of the slice,
    whatever its rows (a T=1 step's and a mixed iteration's alike), three a
    (layer, step) pair, each pair at the window's average load over ALL its
    (layer, step) pairs (`stats()["moe"]`, which counts the same
    population).  Returns (least seconds, the calls' seconds)."""
    kernel = ssm_flops.kernel(run, "moe_grouped_matmul")
    load = moe_flops.window_load(run)
    if not kernel or load is None:
        return None
    f = run["fields"]
    assignments, _, hit, pairs = load
    least = moe_flops.expert_layer_s(
        assignments / pairs, hit / pairs,
        {"d_model": f["d_model"], "d_ff": f["d_expert"]}, peaks)
    return least * kernel["calls"] / 3.0, kernel["seconds"]
