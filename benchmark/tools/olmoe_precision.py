#!/usr/bin/env python3
"""How far the served OLMoE is from its float32 reference, and what a lower
precision would read: the two readings behind `check.max_gap` / `mean_gap`
of `traffic/decode_moe_saturated.json` (PERF.md section 2).  On the chip, one
process, no cluster:

  python3 benchmark/tools/olmoe_precision.py [--seed N] [--requests 3] \
      [--new-tokens 192]

It serves greedy requests through the engine (bf16 weights, paged cache),
then judges the served tokens three times with `reference/olmoe.py`'s own
functions: as the reference is (float32 arithmetic on the served weights);
with the router's input, product and probabilities rounded to bf16 (a router
computed in bf16 only); and with every matrix rounded to an 8-bit float's
mantissa (e4m3's three bits under an ideal per-tensor scale: the nearest
precision under the configuration's bf16).  The last must come out as not
correct under the limits.  It also counts the (position, layer) pairs at
which the program's own bf16 arithmetic over the whole sequence chose other
experts than the reference.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import manifest
from benchmark.reference import olmoe as ref


@functools.partial(jax.jit, static_argnames=("top_k", "router_bf16"))
def ref_layer(x, blocks, i, top_k, router_bf16=False):
    """`ref._layer_jit`, also returning the [L, E] mask of chosen experts;
    `router_bf16` makes it a bf16-only router: its input, its product and
    its probabilities each rounded to bf16."""
    def r16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    with ref.HIGHEST():
        x = ref.attention(x, {k: blocks[k][i] for k in ref._ATTENTION_LEAVES
                              if k in blocks})
        h2 = ref.rms_norm(x, blocks["mlp_norm"][i])
        if router_bf16:
            probs = r16(jax.nn.softmax(
                r16(r16(h2) @ ref.f32(blocks["router"][i])), -1))
            weights = ref.top_k_weights(probs, top_k)
        else:
            weights = ref.router_weights(h2, blocks["router"][i], top_k)
        return x + ref.expert_sum(h2, blocks, i, weights), weights > 0


def judge(params, prompt, output, top_k, router_bf16=False, bucket=256):
    """(gaps, ranks, chosen [layers, L, E]) of one served request."""
    seq = list(prompt) + list(output)
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    x = ref.f32(params["tok_embed"][tokens])
    chosen = []
    for i in range(params["blocks"]["router"].shape[0]):
        x, mask = ref_layer(x, params["blocks"], i, top_k, router_bf16)
        chosen.append(np.asarray(mask))
    logits = ref._head_jit(x, params["final_norm"], params["lm_head"], 8)
    gap, rank = ref._gaps_jit(logits, tokens, len(prompt) - 1)
    first, last = len(prompt) - 1, len(seq) - 1
    return (np.asarray(gap)[first:last], np.asarray(rank)[first:last],
            np.stack(chosen)[:, :len(seq)])


def program_choices(params, tokens, cfg):
    """The program's own arithmetic over the whole sequence (bf16
    activations, `llama.py`'s norm, q/k norm, RoPE, router and expert layer,
    plain attention), layer by layer: [layers, L, E] chosen-expert masks.
    It mirrors `llama._block` to get at the router's choices, which the
    program does not hand out."""
    from ray_tpu.models import llama
    from ray_tpu.ops.attention import reference_attention
    (scanned, _), experts = llama._layer_stack(params["blocks"], cfg)

    @jax.jit
    def layer(x, i, scanned, experts):     # the weights are arguments
        p = {**{k: v[i] for k, v in scanned.items()}, **experts, "layer": i}
        h = llama._rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = llama._qkv(h, p, cfg)
        q, k = (llama._rope(a, cfg.rope_theta) for a in (q, k))
        attn = reference_attention(q, k, v, causal=True)
        x = x + jnp.einsum("blhk,hkd->bld", attn, p["wo"])
        h2 = llama._rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        probs = jax.nn.softmax(jnp.dot(
            h2[0].astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), -1)
        _, top = jax.lax.top_k(probs, cfg.n_experts_per_tok)
        mask = jnp.zeros(probs.shape, bool).at[
            jnp.arange(probs.shape[0])[:, None], top].set(True)
        return x + llama._moe_ffn(h2, p, cfg)[0], mask

    x = params["tok_embed"][jnp.asarray(tokens, jnp.int32)][None].astype(
        cfg.dtype)
    out = []
    for i in range(cfg.n_layers):
        x, mask = layer(x, i, scanned, experts)
        out.append(np.asarray(mask))
    return np.stack(out)


@functools.partial(jax.jit, donate_argnums=0)
def to_8bit_mantissa(a):
    """Three mantissa bits, the exponent kept: an explicit rounding.  (A
    round trip `astype(float8_e4m3fn).astype(bf16)` is no rounding on the
    chip: the TPU compiler removes the pair as excess precision, and the
    first run of this tool read the float32 reference's gaps to the digit.)"""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=192)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from ray_tpu.inference import InferenceEngine
    m = manifest.load()
    cfg = manifest.model_config(m.load_config("olmoe-1b-7b"), None,
                                args.rehearse)
    eng = InferenceEngine("llama", cfg, max_lanes=4, block_size=16,
                          num_blocks=64, max_seq_len=min(512, cfg.max_seq_len),
                          prefill_chunk=32, seed=args.seed, auto_start=False)
    device = jax.devices()[0]
    rng = np.random.default_rng([args.seed, 11])
    served = []
    for _ in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              int(rng.integers(16, 65))).tolist()
        served.append((prompt, eng.generate(prompt, args.new_tokens)))
    params, top_k = eng.params, cfg.n_experts_per_tok
    report = {"device": device.device_kind, "platform": device.platform,
              "seed": args.seed, "requests": args.requests,
              "served_tokens": sum(len(o) for _, o in served)}

    base = [judge(params, p, o, top_k) for p, o in served]
    gaps = np.concatenate([b[0] for b in base])
    report["float32_reference"] = {
        "max_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
        "argmax_pct": 100.0 * float(np.mean(
            np.concatenate([b[1] for b in base]) == 0))}
    pairs = flipped = positions = touched = 0
    for (prompt, out), (_, _, chosen) in zip(served, base):
        mine = program_choices(params, prompt + out, cfg)
        differ = (mine != chosen).any(-1)[:, len(prompt) - 1:]   # [L, pos]
        pairs += differ.size
        flipped += int(differ.sum())
        positions += differ.shape[1]
        touched += int(differ.any(0).sum())
    report["expert_choices"] = {
        "position_layer_pairs": pairs, "pairs_with_another_set": flipped,
        "positions": positions, "positions_with_a_flip_in_some_layer": touched}

    low = [judge(params, p, o, top_k, router_bf16=True) for p, o in served]
    gaps = np.concatenate([b[0] for b in low])
    report["router_in_bf16_only"] = {
        "max_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
        "pairs_with_another_set": int(sum(
            (a[2] != b[2]).any(-1)[:, len(p) - 1:].sum()
            for a, b, (p, _) in zip(low, base, served)))}

    eng.shutdown()
    params["blocks"] = {
        k: to_8bit_mantissa(v) if v.ndim >= 3 else v
        for k, v in params["blocks"].items()}
    params["lm_head"] = to_8bit_mantissa(params["lm_head"])
    params["tok_embed"] = to_8bit_mantissa(params["tok_embed"])
    low = [judge(params, p, o, top_k) for p, o in served]
    gaps = np.concatenate([b[0] for b in low])
    report["weights_at_8bit_mantissa"] = {
        "max_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}
    print(json.dumps(report, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
