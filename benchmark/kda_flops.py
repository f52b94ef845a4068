"""Operations and bytes of Kimi Delta Attention's one-token update and of
its scan, and of a decode step of a stack that has KDA in most layers beside
a few latent-attention layers, over a share of sigmoid-routed experts
(`kimi-linear-48b-a3b`), computed from shapes, from the program's own
counters (`stats()["layers"]`, `["ssm"]`, `["latent"]`, `["prefill"]`,
`["moe"]`) and from the traced slice's kernel calls: the arithmetic behind
the `kda_*` per-layer metrics, kept with the yardstick like `ssm_flops.py`
and `conv_flops.py` (whose counts of latent rows and of a grouped multiply
these are).

Counts are what the PUBLISHED recurrence needs, whatever form a kernel
takes.  A lane's state is heads x d_k x d_v float32 numbers a layer; one
token reads and writes each once and spends 8 operations on it (the decay's
multiply; a multiply-add against k for what the state predicts; a
multiply-add against q; a multiply-add of the rank-one correction k u^T;
the eighth is o's own correction and u, a few operations a row rounded up),
with q, k, the decay's log, v, beta and o once beside it (float32, as the
kernels take them).  A scan of T tokens does the same work a token and
reads and writes a row's state ONCE, whatever T.  An expert that took no
assignment is not read, and experts that live on other chips are not this
chip's to read; a layer's latent rows are read once for all heads.  The
layers of each kind are the program's own count (`stats()["layers"]`:
`state` the KDA layers, `kv` the latent layers, `experts`; the dense
feed-forward layers are the rest), never `n_layers`.
"""

from __future__ import annotations

from benchmark import latent_flops, moe_flops, ssm_flops
# (layers of each kind a step runs, as the program counts them: `state` the
# KDA layers, `kv` the latent layers, `experts`)
from benchmark.conv_flops import layers  # noqa: F401


def state_numbers(f: dict) -> int:
    """Numbers of one lane's recurrent state, one layer."""
    return f["kda_heads"] * f["kda_head_dim"] * f["kda_head_dim"]


def row_numbers(f: dict) -> int:
    """Numbers a token brings to and takes from one layer's recurrence:
    q, k and the decay's log (d_k a head each), v and o (d_v), beta."""
    return f["kda_heads"] * (5 * f["kda_head_dim"] + 1)


def update(lanes: float, f: dict):
    """One layer's one-token update of `lanes` lanes: (flops, bytes).  The
    state float32, read once and written once; the token's rows beside
    it."""
    n = state_numbers(f)
    return 8.0 * lanes * n, 4.0 * lanes * (2 * n + row_numbers(f))


def scan(tokens: float, rows: float, f: dict):
    """One layer's scan of `tokens` tokens in all over `rows` rows (lanes
    that prefill): (flops, bytes).  The recurrence's own operations a
    token; a row's state read and written once."""
    n = state_numbers(f)
    return 8.0 * tokens * n, 4.0 * (rows * 2 * n + tokens * row_numbers(f))


def kda_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """A KDA mixer's projections (q, k, v; the decay's and the gate's
    low-rank pairs; beta; the output), its taps, and its float32 vectors."""
    d, wide, rank = (f["d_model"], f["kda_heads"] * f["kda_head_dim"],
                     f["kda_head_dim"])
    return itemsize * (d * 3 * wide + f["kda_conv"] * 3 * wide
                       + 2 * (d * rank + rank * wide) + d * f["kda_heads"]
                       + wide * d + rank) + 4 * (wide + f["kda_heads"])


def latent_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """A latent layer's direct query, the latent's down projection, the
    absorbed up-projection's two halves and the output projection."""
    d, h = f["d_model"], f["n_heads"]
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    return itemsize * (
        d * h * qk + d * latent_flops.row(f)
        + f["kv_lora_rank"] * h * (f["qk_nope_head_dim"] + f["v_head_dim"])
        + h * f["v_head_dim"] * d)


def step_weight_bytes(f: dict, n: dict, experts_hit: float,
                      itemsize: int = 2) -> float:
    """What one step program reads of its weights: every KDA and latent
    mixer, three matrices of every held expert hit, the shared expert and
    the router in the expert layers, the dense SwiGLU in the others, and
    the untied head."""
    d = f["d_model"]
    dense = n["state"] + n["kv"] - n["experts"]
    return (n["state"] * kda_weight_bytes(f, itemsize)
            + n["kv"] * latent_weight_bytes(f, itemsize)
            + itemsize * (n["experts"] * d * (
                3 * f["d_expert"] * (experts_hit + f["n_shared_experts"])
                + f["n_routed_experts"])
                + dense * 3 * d * f["d_ff"] + d * f["vocab_size"]))


def latent_bytes(f: dict, n: dict, ctx_tokens: float,
                 itemsize: int = 2) -> float:
    """The latent rows of `ctx_tokens` tokens, every latent layer."""
    return itemsize * n["kv"] * ctx_tokens * latent_flops.row(f)


def tail_bytes(f: dict, n: dict, lanes: float, itemsize: int = 2) -> float:
    """The convolutions' tails of `lanes` lanes read and written, every
    KDA layer."""
    return itemsize * n["state"] * lanes * 2 * (f["kda_conv"] - 1) * 3 \
        * f["kda_heads"] * f["kda_head_dim"]


def lanes_per_update(run: dict):
    """Lanes a T=1 population stepped, the window's average
    (`stats()["ssm"]` `tokens_updated` over `stats()["latent"]`
    `decode_steps`)."""
    tokens = ssm_flops.delta(run, "ssm", "tokens_updated")
    steps = ssm_flops.delta(run, "latent", "decode_steps")
    return tokens / steps if tokens and steps else None


def programs(run: dict):
    """Step programs of the traced slice, from its kernel calls: every
    program (the T=1 step and the pair's) runs `kda_update` once a KDA
    layer over its [max_lanes, 1] rows.  Weight reads are counted by this,
    a PROGRAM, and not by update + scan calls: a pair's program holds both
    and reads its weights once."""
    n = (layers(run) or {}).get("state")
    kernel = ssm_flops.kernel(run, "kda_update")
    return kernel["calls"] / n if kernel and n else None


def step_bytes(run: dict):
    """The bytes the traced slice's programs must move, or None where an
    input is missing: per program the weights by the held experts hit (the
    window's average a (layer, step) pair), the latent rows of the latent
    layers at the context the slice's own steps attended over, and the
    states and tails of the lanes it stepped, read and written, in every
    KDA layer.  A chunk's own reads beside them (its rows' attention, its
    rows' states) are left out: a lower count."""
    f, n = run["fields"], layers(run)
    count = programs(run)
    load = latent_flops.held_load(run)
    lanes = lanes_per_update(run)
    context = ssm_flops.slice_context(run)
    if not n or count is None or load is None or lanes is None \
            or context is None:
        return None
    _, hit, pairs = load
    return count * (step_weight_bytes(f, n, hit / pairs)
                    + latent_bytes(f, n, context)
                    + n["state"] * update(lanes, f)[1]
                    + tail_bytes(f, n, lanes))


def grouped_matmul_least_s(run: dict, peaks: dict):
    """Least seconds of the grouped multiplies of the traced slice, ONE
    population on both sides: every `moe_grouped_matmul` call of the slice,
    whatever its rows (a T=1 step's and a pair's alike), three a (layer,
    step) pair, each pair at the window's average load over ALL its (layer,
    step) pairs: the assignments that fell on held experts and the held
    experts hit (`stats()["moe"]` of a share, which counts the same
    population).  Returns (least seconds, the calls' seconds)."""
    kernel = ssm_flops.kernel(run, "moe_grouped_matmul")
    load = latent_flops.held_load(run)
    if not kernel or load is None:
        return None
    f = run["fields"]
    held, hit, pairs = load
    least = moe_flops.expert_layer_s(
        held / pairs, hit / pairs,
        {"d_model": f["d_model"], "d_ff": f["d_expert"]}, peaks)
    return least * kernel["calls"] / 3.0, kernel["seconds"]
