"""Operations and bytes of a sparse-expert layer and of a decode step that
holds one, computed from shapes and from the program's own load counters
(`stats()["moe"]`): the arithmetic behind the `moe_*` per-layer metrics, kept
with the yardstick like `flops.py`.

Counts are what the algorithm needs: an expert that took no assignment is
not read, an expert that took some is read once per matrix whatever the
number of its rows, and activations are read and written once.
"""

from __future__ import annotations

from benchmark import flops


def window_load(run: dict):
    """The window's delta of the engine's expert counters, or None where the
    program has none: (assignments, expert_load[E], experts_hit,
    layer_steps)."""
    m0 = (run.get("stats0") or {}).get("moe")
    m1 = (run.get("stats1") or {}).get("moe")
    if not m0 or not m1:
        return None
    steps = m1["layer_steps"] - m0["layer_steps"]
    if steps <= 0:
        return None
    return (m1["assignments"] - m0["assignments"],
            [b - a for a, b in zip(m0["expert_load"], m1["expert_load"])],
            m1["experts_hit"] - m0["experts_hit"], steps)


def grouped_matmul(rows: float, experts_hit: float, k: int, n: int,
                   itemsize: int = 2):
    """rows [rows, k] grouped by expert times the hit experts' [k, n]:
    every row once, every hit expert's matrix once."""
    return (2.0 * rows * k * n,
            experts_hit * k * n * itemsize + rows * (k + n) * itemsize)


def expert_layer_s(rows: float, experts_hit: float, f: dict,
                   peaks: dict) -> float:
    """Least time of one layer's three grouped multiplies (gate, up, down),
    each against its own bound."""
    d, ff = f["d_model"], f["d_ff"]
    return (2 * flops.roofline_s(*grouped_matmul(rows, experts_hit, d, ff),
                                 peaks)[0]
            + flops.roofline_s(*grouped_matmul(rows, experts_hit, ff, d),
                               peaks)[0])


def layer_weight_bytes(f: dict, experts_hit: float, itemsize: int = 2):
    """What one layer of a step reads of its weights: q, k, v, o
    projections, the router, three matrices of every expert hit."""
    d, dh = f["d_model"], f["d_model"] // f["n_heads"]
    attention = (2 * f["n_heads"] + 2 * f["n_kv_heads"]) * dh * d
    return itemsize * (attention + d * f["n_experts"]
                       + 3 * experts_hit * d * f["d_ff"])


def head_bytes(f: dict, itemsize: int = 2):
    """The untied output head, read whole by every step."""
    return itemsize * f["d_model"] * f["vocab_size"]


def kv_bytes(f: dict, context_tokens: float, itemsize: int = 2):
    """Cached keys and values of `context_tokens` tokens, every layer."""
    dh = f["d_model"] // f["n_heads"]
    return itemsize * 2 * f["n_layers"] * context_tokens * f["n_kv_heads"] * dh
