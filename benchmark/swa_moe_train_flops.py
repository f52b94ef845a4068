"""Operations and bytes of one TRAIN step of a decoder whose layers mix
window and full attention over a share of softmax-routed experts (Mellum 2),
from the configuration's `fields` and the traffic file, and ONE number of
the program's: the rows the held experts multiplied, where the driver
carries them (`drivers/train_state.py` reads the step's `expert_load`; a
share's router does not stay even, PERF.md section 6, PR 61).  Without
them the rows are the EXPECTED ones, tokens x experts a token x held /
routed: what an even router sends to a share.

As `flops.py` counts: what the algorithm needs, not what an implementation
does.  Recomputation (remat, the backward's scores) is not counted; causal
attention is counted once, and a window as a window: a position attends
min(position + 1, window) keys, W L - W (W - 1) / 2 pairs a sequence of L
where W <= L; experts held elsewhere are nobody's work here.  Backward is
twice the forward.
"""

from __future__ import annotations

from benchmark import flops

FULL, WINDOW = "full_attention", "sliding_attention"


def layer_kinds(f: dict) -> tuple:
    """(window layers, full layers) of the configuration's `layer_types`."""
    kinds = list(f["layer_types"])
    return kinds.count(WINDOW), kinds.count(FULL)


def window_pairs(seq: int, window: int) -> float:
    """(query, key) pairs of one causal sequence under a window (the
    position itself counts): W L - W (W - 1) / 2, all L (L + 1) / 2 where
    the window is the sequence or longer."""
    w = min(window, seq)
    return w * seq - w * (w - 1) / 2.0


def held(f: dict) -> int:
    return f.get("n_experts_held") or f["n_routed_experts"]


def expert_rows(f: dict, tokens: int) -> float:
    """The assignments an even router sends to the held experts."""
    return tokens * f["n_experts_per_tok"] * held(f) / f["n_routed_experts"]


def rows_sent(run: dict, part: str):
    """The rows a layer's held experts multiplied a step, the mean over the
    layers and the steps of `part` ("window" or "traced") as the driver
    read them from the step's `expert_load`; None where it carries none."""
    loads = (run.get("expert_rows") or {}).get(part)
    return sum(loads) / len(loads) if loads else None


def forward_flops_per_token(f: dict, seq: int, rows=None) -> dict:
    """Forward FLOPs a token, by part; `rows`: the rows a layer's held
    experts multiply a token (None: an even router's)."""
    d, width = f["d_model"], f["n_heads"] * f["head_dim"]
    kv = f["n_kv_heads"] * f["head_dim"]
    n_window, n_full = layer_kinds(f)
    layers = n_window + n_full
    return {
        "projections": layers * 2.0 * (2 * d * width + 2 * d * kv
                                       + d * f["n_routed_experts"]),
        "window_scores": n_window * 4.0 * width
        * window_pairs(seq, f["sliding_window"]) / seq,
        "full_scores": n_full * 4.0 * width * (seq + 1) / 2,
        "experts": layers * (expert_rows(f, 1) if rows is None else rows)
        * 6.0 * d * f["d_expert"],
        "head": 2.0 * d * f["vocab_size"],
    }


def flops_per_token(f: dict, seq: int, rows=None) -> float:
    """Forward and backward (twice the forward) a trained token."""
    return 3.0 * sum(forward_flops_per_token(f, seq, rows).values())


def window_flash(b: int, h: int, seq: int, dh: int, window: int,
                 itemsize: int = 2) -> tuple:
    """(flops, bytes) of a windowed flash attention's forward and backward
    over [b, seq, h, dh]: `flops.flash_fwd` + `flops.flash_bwd` with the
    window's pairs for the triangle's: two products forward and four
    backward a pair; q, k, v, o read or written four times forward and
    eight backward, a float32 logsumexp each way."""
    pairs = b * h * window_pairs(seq, window)
    work = (2 + 4) * 2.0 * dh * pairs
    nbytes = (4 + 8) * b * seq * h * dh * itemsize + 2 * b * h * seq * 4
    return work, nbytes


def full_flash(b: int, h: int, seq: int, dh: int) -> tuple:
    fwd, bwd = flops.flash_fwd(b, h, seq, dh), flops.flash_bwd(b, h, seq, dh)
    return fwd[0] + bwd[0], fwd[1] + bwd[1]


def grouped_products(f: dict, tokens: int, itemsize: int = 2,
                     rows=None) -> list:
    """[(flops, bytes)] of one expert layer's nine grouped products in a
    train step: gate, up and down, each forward (rows x [K, N]), dx (the
    same product the other way) and dw (rows^T rows a held expert).  Each
    reads its rows in and writes them out once; forward and dx read the
    held experts' matrices once in the activations' dtype, dw writes them
    once in float32.  `rows`: the rows the layer's held experts took
    (None: an even router's)."""
    rows = expert_rows(f, tokens) if rows is None else rows
    d, w = f["d_model"], f["d_expert"]
    work = 2.0 * rows * d * w
    moved = rows * (d + w) * itemsize
    matrices = held(f) * d * w
    return ([(work, moved + matrices * itemsize)] * 6
            + [(work, moved + matrices * 4)] * 3)
