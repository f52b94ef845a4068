"""GPT family: LayerNorm with bias + learned positions + GELU MLP, tied head.

What is the family's own: the config and its presets, the parameter format
(`param_specs`, `init_params`) and `spec`, which names the parts of
models/decoder.py its block is made of.  Everything that runs is the
decoder's, bound to `spec` under the names below.  `n_experts` > 0 swaps the
MLP for the Switch top-1 expert layer (`decoder.switch_moe`): trained and
sharded over the `expert` mesh axis, not served.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoder


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    # Activation dtype.  Params are kept fp32 (training updates them);
    # `serving_params` makes the form the engine's step multiplies.
    dtype: Any = jnp.bfloat16
    n_experts: int = 0               # 0 = dense MLP; >0 = Switch MoE
    capacity_factor: float = 1.25
    remat: bool = False
    tie_embeddings: bool = True
    # lax.scan unroll factor over layers.  Unrolling lets XLA fuse and
    # schedule across layer boundaries (measured +33% on one chip, PERF.md)
    # at the cost of compile time; keep 1 for very deep/remat configs.
    scan_unroll: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads          # no grouped-query attention


CONFIGS = {
    "nano": GPTConfig(vocab_size=512, n_layers=2, d_model=64, n_heads=4,
                      d_ff=128, max_seq_len=128, dtype=jnp.float32),
    "nano-moe": GPTConfig(vocab_size=512, n_layers=2, d_model=64, n_heads=4,
                          d_ff=128, max_seq_len=128, n_experts=4,
                          dtype=jnp.float32),
    "gpt2-small": GPTConfig(scan_unroll=12),       # 124M
    "gpt2-medium": GPTConfig(n_layers=24, d_model=1024, n_heads=16,
                             d_ff=4096, scan_unroll=8),
    "gpt2-xl": GPTConfig(n_layers=48, d_model=1600, n_heads=25, d_ff=6400,
                         scan_unroll=4),
    "7b": GPTConfig(vocab_size=32000, n_layers=32, d_model=4096, n_heads=32,
                    d_ff=11008, max_seq_len=4096, remat=True),
}


def param_specs(config: GPTConfig) -> dict:
    """Logical sharding spec tree, congruent with init_params output."""
    blocks = {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "kv"),
        "wk": ("layers", "embed", "heads", "kv"),
        "wv": ("layers", "embed", "heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
    }
    if config.n_experts:
        blocks.update({
            "router": ("layers", "embed", "experts"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
        })
    else:
        blocks.update({
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    specs = {
        # Table embed dims stay unsharded (vocab carries tensor+fsdp, see
        # parallel/sharding.py DEFAULT_RULES["vocab"]); pos_embed is tiny
        # and replicated.
        "tok_embed": ("vocab", None),
        "pos_embed": (None, None),
        "blocks": blocks,
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    return specs


def init_params(config: GPTConfig, key: jax.Array) -> dict:
    c = config
    n, d, h, dh, f = c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff
    keys = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in))

    blocks = {
        "ln1_scale": jnp.ones((n, d)),
        "ln1_bias": jnp.zeros((n, d)),
        "wq": dense(next(keys), (n, d, h, dh), d),
        "wk": dense(next(keys), (n, d, h, dh), d),
        "wv": dense(next(keys), (n, d, h, dh), d),
        # Residual-branch outputs scaled per GPT-2 (1/sqrt(2*n_layers)).
        "wo": dense(next(keys), (n, h, dh, d), h * dh) / np.sqrt(2 * n),
        "ln2_scale": jnp.ones((n, d)),
        "ln2_bias": jnp.zeros((n, d)),
    }
    if c.n_experts:
        e = c.n_experts
        blocks["router"] = dense(next(keys), (n, d, e), d)
        blocks["w_up"] = dense(next(keys), (n, e, d, f), d)
        blocks["w_down"] = dense(next(keys), (n, e, f, d), f) / np.sqrt(2 * n)
    else:
        blocks["w_up"] = dense(next(keys), (n, d, f), d)
        blocks["w_down"] = dense(next(keys), (n, f, d), f) / np.sqrt(2 * n)

    params = {
        "tok_embed": jax.random.normal(next(keys), (c.vocab_size, d)) * 0.02,
        "pos_embed": jax.random.normal(next(keys), (c.max_seq_len, d)) * 0.01,
        "blocks": blocks,
        "final_ln_scale": jnp.ones((d,)),
        "final_ln_bias": jnp.zeros((d,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, c.vocab_size), d)
    return params


def spec(config: GPTConfig) -> decoder.Spec:
    return decoder.Spec(
        norm=decoder.layernorm,
        attn_norm=("ln1_scale", "ln1_bias"),
        mlp_norm=("ln2_scale", "ln2_bias"),
        final_norm=("final_ln_scale", "final_ln_bias"),
        ffn=decoder.SWITCH if config.n_experts else decoder.GELU,
        tied_head=config.tie_embeddings,
        init_params=init_params, param_specs=param_specs)


# The decoder bound to `spec` (signatures and docs: models/decoder.py,
# less its first argument).  `forward` returns (logits [B, L, V], the
# Switch layers' auxiliary loss), `forward_trunk` (x [B, L, D], the same).
_bound = decoder.bind(spec)
forward_trunk = _bound.forward_trunk
forward = _bound.forward
lm_head = _bound.lm_head
forward_cached = _bound.forward_cached
loss_fn = _bound.loss_fn
serving_params = _bound.serving_params
shard_params = _bound.shard_params
num_params = _bound.num_params
make_train_step = _bound.make_train_step
