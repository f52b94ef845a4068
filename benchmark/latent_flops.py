"""Operations and bytes of latent attention (MLA, absorbed) over a latent
paged cache and of a decode step of a configuration that has it beside a
share of sigmoid-routed experts, computed from shapes and from the program's
own counters (`stats()["latent"]`, `stats()["moe"]`): the arithmetic behind
the `latent_*` and `held_*` per-layer metrics, kept with the yardstick like
`flops.py` and `moe_flops.py`.

Counts are what the algorithm needs.  A cached token's latent row
(kv_lora_rank + qk_rope_head_dim numbers) is read ONCE a layer for all
heads, for the scores and for the values; per head it costs a dot over the
whole row and a weighted sum over the latent part.  An expert that took no
assignment is not read, and experts that live on other chips are not this
chip's to read.
"""

from __future__ import annotations


def window(run: dict, key: str):
    """(stats1[key], stats0[key]) where the program has both, else None."""
    s0 = (run.get("stats0") or {}).get(key)
    s1 = (run.get("stats1") or {}).get(key)
    return (s1, s0) if s0 and s1 else None


def ctx_tokens_per_step(run: dict):
    """Context tokens a T=1 step attended over, all lanes together: the
    window's average from `stats()["latent"]`."""
    w = window(run, "latent")
    if w is None:
        return None
    steps = w[0]["decode_steps"] - w[1]["decode_steps"]
    return (w[0]["ctx_tokens"] - w[1]["ctx_tokens"]) / steps if steps > 0 \
        else None


def held_load(run: dict):
    """The window's delta of the expert counters of a share: (assignments
    held here, experts hit, (layer, step) pairs), or None."""
    w = window(run, "moe")
    if w is None or "assignments_held" not in w[0]:
        return None
    pairs = w[0]["layer_steps"] - w[1]["layer_steps"]
    if pairs <= 0:
        return None
    return (w[0]["assignments_held"] - w[1]["assignments_held"],
            w[0]["experts_hit"] - w[1]["experts_hit"], pairs)


def row(f: dict) -> int:
    return f["kv_lora_rank"] + f["qk_rope_head_dim"]


def latent_decode(ctx_tokens: float, lanes: int, f: dict, itemsize: int = 2):
    """One layer's single-query latent attention of `lanes` lanes over
    `ctx_tokens` cached tokens in all: per cached token and head a dot over
    the row and a weighted sum over the latent (64 x (576 + 512) x 2 FLOPs a
    token for A.X-K1); each row read once (1,152 bytes), q rows and latent
    outputs once per lane."""
    per_head = row(f) + f["kv_lora_rank"]
    flops = 2.0 * ctx_tokens * f["n_heads"] * per_head
    nbytes = itemsize * (ctx_tokens * row(f)
                         + lanes * f["n_heads"] * per_head)
    return flops, nbytes


def attention_weight_bytes(f: dict, itemsize: int = 2) -> int:
    """q down and up, kv down, the absorbed up-projection's two halves and
    the output projection of one layer."""
    d, h = f["d_model"], f["n_heads"]
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    return itemsize * (
        d * f["q_lora_rank"] + f["q_lora_rank"] * h * qk + d * row(f)
        + f["kv_lora_rank"] * h * (f["qk_nope_head_dim"] + f["v_head_dim"])
        + h * f["v_head_dim"] * d)


def expert_layer_weight_bytes(f: dict, experts_hit: float,
                              itemsize: int = 2) -> float:
    """What one expert layer of a step reads of its weights: attention, the
    router, the shared expert, three matrices of every held expert hit."""
    d, fe = f["d_model"], f["d_expert"]
    return attention_weight_bytes(f, itemsize) + itemsize * (
        d * f["n_routed_experts"]
        + 3 * d * fe * (f["n_shared_experts"] + experts_hit))


def dense_layer_weight_bytes(f: dict, itemsize: int = 2) -> int:
    return attention_weight_bytes(f, itemsize) \
        + itemsize * 3 * f["d_model"] * f["d_ff"]


def head_bytes(f: dict, itemsize: int = 2) -> int:
    return itemsize * f["d_model"] * f["vocab_size"]


def cache_bytes(f: dict, ctx_tokens: float, itemsize: int = 2) -> float:
    """The latent rows of `ctx_tokens` tokens, every layer."""
    return itemsize * f["n_layers"] * ctx_tokens * row(f)
