"""Operations and bytes of a decode step of a stack whose layers are ONE
part each (`nemotron-3-nano-30b-a3b`: a state-space mixer, or grouped-query
attention, or squared-ReLU experts beside a shared one), computed from
shapes, from the program's own counters (`stats()["moe"]`, `["ssm"]`,
`["paged"]`, `["prefill"]`) and from the traced slice's kernel calls: the
arithmetic behind the `hybrid_*` and `relu2_*` per-layer metrics, kept with
the yardstick like `ssm_flops.py` and `moe_flops.py` (whose counts of the
update, the scan, single-query attention and a grouped multiply these are).

Counts are what the algorithm needs, whatever implements it: an expert is
its PUBLISHED two matrices (d_model x d_expert and back), read once a step
where an assignment hit it and not at all where none did, whatever padding
or tiling the kernel moves them in; the layers of each kind are the program's
own count (`stats()["layers"]`: `state`, `kv`, `experts`), never `n_layers`
and no family's pattern letters, so the readers take any stack of one-part
layers.
"""

from __future__ import annotations

from benchmark import flops, latent_flops, moe_flops, ssm_flops


def layers(run: dict):
    """Layers of each kind a step runs (`state`: mixers, `kv`: attention,
    `experts`), as the program counts them, or None where it does not."""
    n = (run.get("stats1") or {}).get("layers")
    return n if n and all(k in n for k in ("state", "kv", "experts")) \
        else None


def relu2_layer_s(rows: float, experts_hit: float, f: dict,
                  peaks: dict) -> float:
    """Least time of one expert layer's two grouped multiplies (up, down)
    over `rows` assignments on `experts_hit` experts, each against its own
    bound."""
    d, fe = f["d_model"], f["d_expert"]
    return sum(flops.roofline_s(
        *moe_flops.grouped_matmul(rows, experts_hit, k, n), peaks)[0]
        for k, n in ((d, fe), (fe, d)))


def expert_layer_weight_bytes(f: dict, experts_hit: float,
                              itemsize: int = 2) -> float:
    """What one expert layer of a step reads of its weights: two matrices
    of every held expert hit, the shared expert's two, the router."""
    d = f["d_model"]
    return itemsize * d * (2 * experts_hit * f["d_expert"]
                           + 2 * f["d_shared"] + f["n_routed_experts"])


def mixer_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """A mixer layer's two projections and its convolution."""
    d = f["d_model"]
    d_ssm = f["ssm_heads"] * f["ssm_head_dim"]
    width = ssm_flops.conv_width(f)
    return itemsize * (d * (d_ssm + width + f["ssm_heads"]) + d_ssm * d
                       + (f["ssm_conv"] + 1) * width)


def attention_weight_bytes(f: dict, itemsize: int = 2) -> int:
    return itemsize * f["d_model"] * f["head_dim"] * (
        2 * f["n_heads"] + 2 * f["n_kv_heads"])


def step_weight_bytes(f: dict, n: dict, experts_hit: float) -> float:
    """What one step reads of its weights: every layer by its kind (`n`:
    `layers`), and the head."""
    return (n["experts"] * expert_layer_weight_bytes(f, experts_hit)
            + n["state"] * mixer_weight_bytes(f)
            + n["kv"] * attention_weight_bytes(f)
            + 2 * f["d_model"] * f["vocab_size"])


def kv_bytes(f: dict, n: dict, ctx_tokens: float, itemsize: int = 2) -> float:
    """The K and V rows of `ctx_tokens` tokens, every attention layer."""
    return itemsize * n["kv"] * ctx_tokens * 2 * f["n_kv_heads"] \
        * f["head_dim"]


def steps(run: dict):
    """(T=1 steps, T>1 steps) of the traced slice, from its kernel calls:
    the update runs once a mixer layer of a T=1 step, the scan once a mixer
    layer of a prefill step."""
    n = (layers(run) or {}).get("state")
    update = ssm_flops.kernel(run, "ssm_update")
    scan = ssm_flops.kernel(run, "ssm_scan")
    if not update or not n:
        return None
    return update["calls"] / n, (scan["calls"] / n if scan else 0.0)


def step_bytes(run: dict):
    """The bytes the traced slice's steps must move, or None where an
    input is missing."""
    f = run["fields"]
    counted = steps(run)
    load = latent_flops.held_load(run)
    lanes = ssm_flops.lanes_per_update(run)
    context = ssm_flops.slice_context(run)
    if counted is None or load is None or lanes is None or context is None:
        return None
    decode, prefill = counted
    _, hit, pairs = load
    n = layers(run)
    nbytes = ((decode + prefill) * step_weight_bytes(f, n, hit / pairs)
              + decode * (n["state"] * ssm_flops.update(lanes, f)[1]
                          + kv_bytes(f, n, context)))
    per = ssm_flops.per_prefill_step(run)
    if prefill and per:
        nbytes += prefill * n["state"] * ssm_flops.scan(0.0, per[1], f)[1]
    return nbytes
