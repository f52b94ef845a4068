"""Operations and bytes of indexed (sparse) and window latent attention over
a paged cache of several kinds of layer, and of a decode step of a
configuration that has both beside a share of sigmoid-routed experts
(`dots3-note-prev`), computed from shapes and from the program's own
counters (`stats()["sparse"]`, `stats()["windows"]`, `stats()["moe"]`): the
arithmetic behind the `sparse_*` and `window_*` per-layer metrics, kept with
the yardstick like `latent_flops.py`.

Counts are what the algorithm needs.  A full layer's T=1 step reads, a lane,
ONE index key (index_head_dim numbers) of every cached token and the latent
rows (kv_lora_rank + qk_rope_head_dim numbers) of the index_topk positions
it chose, never the context's; a window layer the rows of its last
sliding_window positions.  `stats()["sparse"]` sums, per T=1 step and for
ONE layer of each kind, the context tokens scored (`ctx_tokens`), the rows
chosen (`rows_chosen`) and the window rows attended (`window_rows`) over the
step's lanes.
"""

from __future__ import annotations

import re

from benchmark.latent_flops import held_load, window  # noqa: F401

FULL, WINDOW = "full_attention", "sliding_attention"


def per_step(run: dict):
    """The window's averages per T=1 step, all lanes together: (context
    tokens scored, rows chosen, window rows attended), or None."""
    w = window(run, "sparse")
    if w is None:
        return None
    steps = w[0]["decode_steps"] - w[1]["decode_steps"]
    if steps <= 0:
        return None
    return tuple((w[0][k] - w[1][k]) / steps
                 for k in ("ctx_tokens", "rows_chosen", "window_rows"))


def layers(f: dict) -> tuple:
    """(leading dense layers, full expert layers, window layers)."""
    kinds = f["layer_types"][f["first_dense_layers"]:]
    return f["first_dense_layers"], kinds.count(FULL), kinds.count(WINDOW)


def sizes(f: dict, kind: str) -> dict:
    """MLA's sizes of a layer of `kind` under the full layers' names."""
    if kind == FULL:
        return {k: f[k] for k in ("n_heads", "q_lora_rank", "kv_lora_rank",
                                  "qk_nope_head_dim", "qk_rope_head_dim",
                                  "v_head_dim")}
    return {k: f["swa_" + k] for k in ("n_heads", "q_lora_rank",
                                       "kv_lora_rank", "qk_nope_head_dim",
                                       "qk_rope_head_dim", "v_head_dim")}


def row(s: dict) -> int:
    return s["kv_lora_rank"] + s["qk_rope_head_dim"]


def index_scores(ctx_tokens: float, lanes: int, f: dict, itemsize: int = 2):
    """One full layer's index scores of `lanes` lanes over `ctx_tokens`
    cached keys in all: per key and index head a dot over the key, a ReLU
    and a weighted add (64 x (2 x 128 + 2) FLOPs a key); each key read once
    (256 bytes), a float32 score written a key, the queries and their
    weights once a lane."""
    hi, di = f["index_n_heads"], f["index_head_dim"]
    flops = ctx_tokens * hi * (2.0 * di + 2)
    nbytes = ctx_tokens * (itemsize * di + 4) \
        + lanes * hi * (itemsize * di + 4)
    return flops, nbytes


def latent_rows(rows: float, lanes: int, s: dict, itemsize: int = 2):
    """Single-query latent attention of `lanes` lanes over `rows` latent
    rows in all (chosen rows, or a window's): per row and head a dot over
    the row and a weighted sum over the latent; each row read once, q rows
    and latent outputs once a lane."""
    per_head = row(s) + s["kv_lora_rank"]
    flops = 2.0 * rows * s["n_heads"] * per_head
    nbytes = itemsize * (rows * row(s) + lanes * s["n_heads"] * per_head)
    return flops, nbytes


def attention_weight_bytes(f: dict, kind: str, itemsize: int = 2) -> int:
    """q down and up, kv down, the absorbed up-projection's two halves, the
    head gate and the output projection of one layer; a full layer's
    indexer besides (its queries' up-projection, the key's and the
    weights' projections)."""
    s, d = sizes(f, kind), f["d_model"]
    h = s["n_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    n = (d * s["q_lora_rank"] + s["q_lora_rank"] * h * qk + d * row(s)
         + s["kv_lora_rank"] * h * (s["qk_nope_head_dim"] + s["v_head_dim"])
         + d * h + h * s["v_head_dim"] * d)
    if kind == FULL:
        hi, di = f["index_n_heads"], f["index_head_dim"]
        n += s["q_lora_rank"] * hi * di + d * di + d * hi
    return itemsize * n


def expert_bytes(f: dict, experts_hit: float, itemsize: int = 2) -> float:
    """The router, the shared expert, three matrices of every held expert
    hit."""
    d, fe = f["d_model"], f["d_expert"]
    return itemsize * (d * f["n_routed_experts"]
                       + 3 * d * fe * (f["n_shared_experts"] + experts_hit))


def step_weight_bytes(f: dict, experts_hit: float, itemsize: int = 2):
    """What one step reads of its weights: every layer's attention, the
    leading layers' dense feed-forward, every expert layer's router, shared
    expert and held experts hit, and the head."""
    lead, full, win = layers(f)
    return ((lead + full) * attention_weight_bytes(f, FULL, itemsize)
            + win * attention_weight_bytes(f, WINDOW, itemsize)
            + lead * itemsize * 3 * f["d_model"] * f["d_ff"]
            + (full + win) * expert_bytes(f, experts_hit, itemsize)
            + itemsize * f["d_model"] * f["vocab_size"])


def step_cache_bytes(f: dict, ctx: float, chosen: float, window_rows: float,
                     itemsize: int = 2) -> float:
    """What one T=1 step reads of the caches: every full layer the index
    keys of the context and the chosen rows, every window layer its
    window's rows."""
    lead, full, win = layers(f)
    return itemsize * (
        (lead + full) * (ctx * f["index_head_dim"]
                         + chosen * row(sizes(f, FULL)))
        + win * window_rows * row(sizes(f, WINDOW)))


def kernel(run: dict, name: str):
    """{calls, seconds} of the Mosaic kernel `name` in the traced slice."""
    k = ((run.get("trace") or {}).get("kernels") or {}).get(name)
    return k if k and k["seconds"] else None


_SELECT = re.compile(r"^sort \w+\[\d+,\d+\]")


def select_seconds(run: dict):
    """Device seconds of the choice in the traced slice: `jax.lax.top_k`
    over [rows, context] is on a TPU one stable sort of the scores with
    their positions, the only two-dimensional sort a step has (the expert
    dispatch sorts one row of assignments)."""
    table = (run.get("trace") or {}).get("ops_table")
    if not table:
        return None
    return sum(s for label, s in table if _SELECT.match(label)) or None
