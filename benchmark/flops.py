"""Operations and bytes computed from shapes: the yardstick's own arithmetic,
kept here so that no PR that claims a gain can change it.

All counts are what the algorithm needs, not what an implementation does:
recomputation is not counted, and causal attention is counted once (the
lower triangle), so a kernel that computes the masked half too shows it as a
lower roofline share.
"""

from __future__ import annotations


def matmul_params(f: dict) -> int:
    """Weights that take part in a matrix multiplication per token: the
    attention and MLP projections of every layer and the tied lm head.
    Embedding look-ups, position table and layer norms do no matmul."""
    d, ff = f["d_model"], f["d_ff"]
    return f["n_layers"] * (4 * d * d + 2 * d * ff) + d * f["vocab_size"]


def num_params(f: dict) -> int:
    """All parameters of the GPT-2 block stack as the program lays it out
    (no projection biases, tied embeddings)."""
    d, ff = f["d_model"], f["d_ff"]
    per_layer = 4 * d * d + 2 * d * ff + 4 * d
    return (f["n_layers"] * per_layer + f["vocab_size"] * d
            + f["max_seq_len"] * d + 2 * d)


def attention_flops_per_token(f: dict, seq: int) -> float:
    """Forward QK^T and PV of every layer for one token of a causal sequence
    of `seq` tokens, averaged over positions: 2 matmuls x 2 FLOPs x d_model
    x (seq + 1) / 2 keys."""
    return f["n_layers"] * 4.0 * f["d_model"] * (seq + 1) / 2


def forward_flops_per_token(f: dict, seq: int) -> float:
    return 2.0 * matmul_params(f) + attention_flops_per_token(f, seq)


def flops_per_token(f: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token."""
    return 3.0 * forward_flops_per_token(f, seq)


def mfu(f: dict, seq: int, tokens_per_s: float, chips: int,
        peak_flops: float) -> float:
    return flops_per_token(f, seq) * tokens_per_s / (chips * peak_flops)


# -- kernels: (flops, bytes) of one call, and the roofline ------------------

def flash_fwd(b, h, s, dh, itemsize=2):
    """Causal flash attention forward over [b, s, h, dh]: QK^T and PV on
    the lower triangle; reads q, k, v, writes o and a float32 logsumexp."""
    flops = 2 * 2.0 * b * h * dh * s * (s + 1) / 2
    nbytes = 4 * b * s * h * dh * itemsize + b * h * s * 4
    return flops, nbytes


def flash_bwd(b, h, s, dh, itemsize=2):
    """The backward of the same: dV = P^T dO, dP = dO V^T, dQ = dS K,
    dK = dS^T Q are needed (4 matmuls); recomputing S = QK^T in each of the
    two backward kernels is the implementation's choice and is not counted.
    Reads q, k, v, o, do and the logsumexp, writes dq, dk, dv."""
    flops = 4 * 2.0 * b * h * dh * s * (s + 1) / 2
    nbytes = 8 * b * s * h * dh * itemsize + b * h * s * 4
    return flops, nbytes


def paged_decode(context_tokens, lanes, h, dh, itemsize=2):
    """Single-query attention of `lanes` lanes over `context_tokens` cached
    tokens in all: q.K and p.V per cached token and head; reads each cached
    key and value once, q and o once per lane."""
    flops = 2 * 2.0 * context_tokens * h * dh
    nbytes = (2 * context_tokens + 2 * lanes) * h * dh * itemsize
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound applies."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
