"""Plain reference for Kimi-Linear-48B-A3B (`model_type` kimi_linear): the
forward pass over a whole sequence in straightforward `jax.numpy`, float32
throughout, matrix multiplications at `highest` precision, no kernel, no
cache, no tail, no chunks, no batching of lanes, no `top_k` primitive, no
dispatch: the short convolutions are a pad-and-sum over the sequence, the
KDA recurrence a `lax.scan` over POSITIONS exactly as written below, latent
attention EXPANDED (every head's keys and values made from the latent, one
masked softmax over every key), every held expert multiplies every token,
the result weighted by the router's weight for it (zero where the token did
not choose it).

The equations (the Kimi Linear report, arXiv:2510.26692, and the family's
published modelling code; every norm an RMSNorm with eps 1e-5 and a plain
learned scale; no bias on any projection):

  x_0 = E[token]
  a layer:  x = x + mix(N1(x))     N1 input_layernorm
            x = x + ffn(N2(x))     N2 post_attention_layernorm
  mix, by the published lists (1-indexed):
    kda_layers   H = 32 heads, d_k = d_v = 128; with h = N1(x):
        q = silu(conv4(h W_q)), k = silu(conv4(h W_k)), v = silu(conv4(h W_v))
            (each conv4 causal, depthwise, 4 taps, zeros before the
            sequence, no bias); a head at a time
            q <- q / sqrt(|q|^2 + 1e-6) * 128^-0.5,
            k <- k / sqrt(|k|^2 + 1e-6)
        g = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias)   [32, 128]
            alpha = exp(g), a number a KEY CHANNEL
        beta = sigmoid(h W_b)                                       [32]
        a head, S [d_k, d_v] from zeros:
            S~  = diag(alpha_t) S_{t-1}
            S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
            o_t = S_t^T q_t
        y = (rmsnorm_head(o) * o_norm * sigmoid((h W_ga) W_gb)) W_o
    full_attn_layers   32 heads, NO positional encoding:
        q = h W_q (192 a head);  [c | k_pe] = h W_kva (512 | 64);
        c <- rmsnorm(c);  per head [k_nope | v] = c W_kvb (128 | 128);
        k = [k_nope | k_pe] (the one k_pe shared by every head, NOT
        rotated, as the query's last 64 are not);
        o = softmax_{s <= t}(q . k_s * 192^-0.5) v;  out = concat(o) W_o
  ffn:  the first `first_k_dense_replace` layers
                    (silu(h W_gate) * (h W_up)) W_down            (9216)
        the others  s = sigmoid(float32(h) W_r) over the 256 experts;
                    chosen = the 8 of largest s + e_score_correction_bias
                    (for the choice only; ties: the lower index;
                    num_expert_group 1 and topk_group 1: a plain top-8);
                    w_e = s_e / sum_chosen s * routed_scaling_factor (2.446)
                    ffn(h) = sum_{e chosen} w_e expert_e(h) + shared(h)
                    (each a SwiGLU of 1024)
  logits = N(x_L) W_head

Which layers the parameters hold is read from the configuration's file
(`benchmark/configs/kimi-linear-48b-a3b.json`: `linear_attn_config`'s
`kda_layers` and `full_attn_layers`, `first_k_dense_replace`), so this
computes the stage that is served, not a guess at it; the nano model of the
rehearsal and the tests (hidden size 64) goes by the same file's
`rehearsal_fields`.

Departures from the published description, each at its line below: the
SHARE in `experts` (the parameters hold experts `experts_offset` to
`experts_offset` + held of the router's 256, the router chooses among all
256, and an assignment to an expert that is not held adds nothing: it is
another chip's part of the sum; the shared expert is on every chip and is
counted here) and the vocabulary's slice (simply a smaller vocabulary).
Values the catalog's config lacks (the file's `assumed` has each): no bias
on `W_gb`; the 1e-6 under the L2 norms and no epsilon under the chosen
scores' sum; the order [q | k | v] of the fused projection's columns and of
the convolution's taps beside it (`w_qkv`, `conv_w`: the program's layout
of the three published matrices side by side).

It takes the parameters in the program's own layout (a stack of leaves for
each kind of layer, mixer x feed-forward: `dense_kdas`, `dense_mlas`,
`kdas`, `mlas`) in whatever dtype they are served from and upcasts a slice
at a time, so that a 6.7k-token request runs in the memory a replica has
left beside its weights, pool and states.  It shares no code with the
program (`ray_tpu/`); the float32 upcast, the SwiGLU's slices and the gap
between two rows of logits are `benchmark/reference/axk1.py`'s, the RMSNorm
and the rotation (used only by a wrong mechanism below)
`benchmark/reference/dots3.py`'s, the router's weights
`benchmark/reference/lfm2.py`'s, a projection's product in blocks of columns
and the untied head `benchmark/reference/nemotronh.py`'s (the same equations
there).

`sizes_of(..., **over)` takes the WRONG mechanisms that
`benchmark/tools/kimilinear_precision.py` and `tests/test_kimilinear.py`
hold the limits against: `delta` false (the correction left out: u = beta
v), `decay` "head" (every channel of a head decayed by the head's mean g),
`beta_one`, `l2` false (q only scaled, k as it is), `rotate` true (q's
last 64 and k_pe rotated at theta 10000), `state_dtype` "bfloat16" (the
state rounded to bf16 behind every step).
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32, swiglu
from benchmark.reference.dots3 import _blocks, rms_norm, rope
from benchmark.reference.lfm2 import router_weights
from benchmark.reference.nemotronh import _head_jit, matmul

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "kimi-linear-48b-a3b.json")
# (mixer, dense feed-forward?) -> the stack of leaves of that kind
STACKS = {("kda", True): "dense_kdas", ("mla", True): "dense_mlas",
          ("kda", False): "kdas", ("mla", False): "mlas"}
L2_EPS = 1e-6           # under the L2 norms of q and k (`assumed`)
HEAD_GROUP = 8          # query heads whose scores are alive at once


@functools.lru_cache(maxsize=None)
def _sizes_by_width() -> dict:
    """Hidden size -> what the parameters do not say, from the
    configuration's file: the served stage's, and the nano model's."""
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    lists = cfg["linear_attn_config"]
    served = {"kda_layers": tuple(lists["kda_layers"]),
              "full_attn_layers": tuple(lists["full_attn_layers"]),
              "dense": cfg["first_k_dense_replace"],
              "eps": cfg["rms_norm_eps"],
              "top_k": cfg["num_experts_per_token"],
              "routed_scale": float(cfg["routed_scaling_factor"]),
              "experts_offset": cfg["fields"]["experts_offset"],
              "delta": True, "decay": "channel", "beta_one": False,
              "l2": True, "rotate": False, "state_dtype": "float32"}
    nano = cfg["rehearsal_fields"]
    return {cfg["hidden_size"]: served, nano["d_model"]: dict(
        served, kda_layers=tuple(nano["kda_layers"]),
        full_attn_layers=tuple(nano["full_attn_layers"]),
        dense=nano["first_dense_layers"], top_k=nano["n_experts_per_tok"],
        experts_offset=nano["experts_offset"])}


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(_sizes_by_width()[d], **over).items()))


def kinds_of(kda_layers, full_attn_layers, dense: int) -> list:
    """(stack, index in it, mixer, dense?) of every layer in order, by the
    published 1-indexed lists."""
    seen: dict = {}
    out = []
    for i in range(len(kda_layers) + len(full_attn_layers)):
        mix = "kda" if i + 1 in kda_layers else "mla"
        if (i + 1 in kda_layers) == (i + 1 in full_attn_layers):
            raise ValueError(f"layer {i + 1} is in both lists or in neither")
        stack = STACKS[mix, i < dense]
        out.append((stack, seen.get(stack, 0), mix, i < dense))
        seen[stack] = seen.get(stack, 0) + 1
    return out


def short_conv(x, w):
    """x [L, C] through a causal depthwise convolution of taps w [K, C]:
    a pad and a sum over the sequence, then SiLU."""
    length, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return jax.nn.silu(sum(f32(w[j]) * padded[j:j + length]
                           for j in range(taps)))


def kda_recurrence(q, k, v, g, beta, delta: bool = True,
                   state_dtype: str = "float32"):
    """The recurrence as published, a position at a time: q, k, g
    [L, H, d_k], v [L, H, d_v], beta [L, H] -> o [L, H, d_v].  (`delta`
    false: the correction left out; `state_dtype` bfloat16: the state
    rounded behind every step.  For the precision tool alone.)"""
    heads, d_k = q.shape[1:]

    def step(state, row):                # state [H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = row
        decayed = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t) if delta else 0.0
        u = beta_t[:, None] * (v_t - seen)
        state = decayed + k_t[:, :, None] * u[:, None, :]
        if state_dtype == "bfloat16":
            # (not `astype` there and back: compiled for the chip that
            # rounding is dropped as excess precision)
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d_k, v.shape[-1])),
                        (q, k, v, g, beta))
    return o


def kda(h, p, s: dict):
    """h [L, D] = N1(x) through Kimi Delta Attention."""
    length = h.shape[0]
    heads = p["A_log"].shape[0]
    wide = p["w_fb"].shape[1]
    d_k = wide // heads
    # (the fused projection's columns and the taps beside them: q | k | v)
    qkv = short_conv(matmul(h, p["w_qkv"]), p["conv_w"])
    q, k, v = (qkv[:, i * wide:(i + 1) * wide].reshape(length, heads, d_k)
               for i in range(3))
    if s["l2"]:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * d_k ** -0.5
    g = -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(
        ((h @ f32(p["w_fa"])) @ f32(p["w_fb"])
         + f32(p["dt_bias"])).reshape(length, heads, d_k))
    if s["decay"] == "head":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ f32(p["w_beta"]))
    if s["beta_one"]:
        beta = jnp.ones_like(beta)
    o = kda_recurrence(q, k, v, g, beta, s["delta"], s["state_dtype"])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + s["eps"]) \
        * f32(p["o_norm"])
    gate = jax.nn.sigmoid((h @ f32(p["w_ga"])) @ f32(p["w_gb"]))
    return matmul(o.reshape(length, wide) * gate, p["w_out"])


def latent_attention(h, p, s: dict):
    """h [L, D] = N1(x) through multi-head latent attention, expanded: a
    group of heads and a block of queries at a time."""
    length, d = h.shape
    _, heads, qk = p["wq"].shape
    lora = p["kv_norm"].shape[0]
    pe = p["w_kva"].shape[1] - lora
    nope = qk - pe
    kv = h @ f32(p["w_kva"])
    c = rms_norm(kv[:, :lora], p["kv_norm"], s["eps"])
    k_pe = kv[:, lora:]
    if s["rotate"]:                     # a wrong mechanism: see the module's
        k_pe = rope(k_pe[:, None, :], 10000.0)[:, 0]
    kpos = jnp.arange(length)
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    block = _blocks(length)

    def heads_of(w, g, axis=1):
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, axis))

    def one_group(g, out):
        q = jnp.einsum("ld,dhk->lhk", h, heads_of(p["wq"], g))
        if s["rotate"]:
            q = jnp.concatenate([q[..., :nope],
                                 rope(q[..., nope:], 10000.0)], -1)
        both = jnp.einsum("lc,chk->lhk", c, heads_of(p["w_kvb"], g))
        k = jnp.concatenate([both[..., :nope], jnp.broadcast_to(
            k_pe[:, None, :], (length, group, pe))], -1)
        v = both[..., nope:]
        w_o = heads_of(p["wo"], g, 0)

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            qpos = b * block + jnp.arange(block)
            keep = kpos[None, :] <= qpos[:, None]
            scores = jnp.einsum("qhk,shk->hqs", rows(q), k) * qk ** -0.5
            scores = jnp.where(keep[None], scores, -jnp.inf)
            o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
            add = o.reshape(block, -1) @ w_o.reshape(-1, d)
            return jax.lax.dynamic_update_slice_in_dim(
                out, rows(out) + add, b * block, 0)

        return jax.lax.fori_loop(0, length // block, one_block, out)

    return jax.lax.fori_loop(0, heads // group, one_group, jnp.zeros_like(h))


def experts(h2, p, s: dict):
    """h2 [L, D] = N2(x) through one expert layer's leaves `p`: every HELD
    expert on every token, masked by the router's weight for it, and the
    shared expert."""
    weights = router_weights(h2, p["router"], p["router_bias"], s["top_k"],
                             s["routed_scale"], 0.0)
    # (the share: experts offset .. offset + held of the router's; what the
    # router gave the others is another chip's to add)
    offset = s["experts_offset"]

    def one(e, acc):
        out = swiglu(h2, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        return acc + jax.lax.dynamic_slice_in_dim(weights, offset + e, 1,
                                                  1) * out

    routed = jax.lax.fori_loop(0, p["w_gate"].shape[0], one,
                               jnp.zeros_like(h2))
    return routed + swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])


@functools.partial(jax.jit, static_argnames=("mix", "dense", "sizes"))
def _layer_jit(x, stack, layer, mix, dense, sizes):
    s = dict(sizes)
    with HIGHEST():
        p = {k: v[layer] for k, v in stack.items()}
        h = rms_norm(x, p["attn_norm"], s["eps"])
        x = x + (kda(h, p, s) if mix == "kda"
                 else latent_attention(h, p, s))
        h2 = rms_norm(x, p["mlp_norm"], s["eps"])
        return x + (swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])
                    if dense else experts(h2, p, s))


def hidden(params, tokens, **over):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer.  `over`: constants other
    than the configuration file's, and the wrong mechanisms."""
    sizes = sizes_of(params, **over)
    s = dict(sizes)
    kinds = kinds_of(s["kda_layers"], s["full_attn_layers"], s["dense"])
    held = {stack: int(leaves["attn_norm"].shape[0])
            for stack, leaves in params.items() if stack in STACKS.values()}
    want = {stack: sum(k[0] == stack for k in kinds) for stack in held}
    if held != want or len(held) != len({k[0] for k in kinds}):
        raise ValueError(f"the parameters hold {held}, the configuration's "
                         f"lists make {want}")
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    for stack, layer, mix, dense in kinds:
        x = _layer_jit(x, params[stack], layer, mix, dense, sizes)
    return x


def row_logits(params, tokens, rows=None, **over):
    """tokens [L] -> logits [L, V] (over the vocabulary slice the
    parameters hold); with `rows` (start, count), of those rows alone."""
    x = hidden(params, tokens, **over)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    vocab = params["lm_head"].shape[1]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(x, params["final_norm"], params["lm_head"], chunks,
                     dict(sizes_of(params))["eps"])


def logits(params, tokens, **over):
    """tokens [B, L] -> logits [B, L, V], a sequence at a time."""
    return jnp.stack([row_logits(params, row, **over)
                      for row in np.asarray(tokens)])


def served_token_gaps(params, prompt, output, bucket: int = 512, **over):
    """One full forward over prompt + served output, from token 0.  Returns,
    for every generated position, (gap, rank): the reference's largest
    logit minus its logit of the served token, and how many tokens the
    reference ranks above the served one (0 = the reference's own greedy
    choice).  The sequence is padded at its end to a multiple of `bucket`
    so that a few compiled programs serve every length; attention, the
    convolutions and the recurrence are causal, so what follows a position
    cannot change it.  Only the generated positions' rows go through the
    head."""
    seq = list(prompt) + list(output)
    first, n = len(prompt) - 1, len(output)
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    count = -(-n // 256) * 256          # rows through the head, bucketed
    start = max(0, min(first, tokens.shape[0] - count))
    rows = row_logits(params, tokens, rows=(start, min(count,
                                                       tokens.shape[0])),
                      **over)
    nxt = jnp.asarray((seq + [0] * tokens.shape[0])[
        start + 1:start + 1 + rows.shape[0]], jnp.int32)
    gap, rank = _gaps_jit(rows, nxt)
    lo = first - start
    return (np.asarray(gap)[lo:lo + n].tolist(),
            np.asarray(rank)[lo:lo + n].tolist())
