"""What can be checked about the chip without one.

libtpu compiles ahead of time for a v5e topology with no device present,
which is enough to catch a program XLA refuses to lower for real TPUs — the
interpreted kernels of the CPU suite lower to plain HLO and hide that.  AOT
says nothing about numerics, device ownership or the process model;
`chip_smoke.py` covers those on the chip.
"""

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private.accelerators import (
    ChipAllocator, chip_env, leasable)
from ray_tpu.models import gpt
from ray_tpu.ops.attention import paged_decode_attention
from ray_tpu.parallel import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import named_sharding, tree_shardings

# One layer, tileable by the flash kernel (L % 128 == 0, head_dim 64).
CFG = gpt.GPTConfig(vocab_size=512, n_layers=1, d_model=128, n_heads=2,
                    d_ff=256, max_seq_len=256)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


@pytest.fixture
def as_on_chip(monkeypatch):
    """Path selection reads the default backend at trace time; make it
    answer as it will on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile_train_step(devices, mesh_cfg):
    mesh = create_mesh(mesh_cfg, devices=devices)
    _, train_step = gpt.make_train_step(
        CFG, optax.sgd(1e-3), mesh if len(devices) > 1 else None)

    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(lambda k: gpt.init_params(CFG, k), jax.random.key(0)),
        tree_shardings(mesh, gpt.param_specs(CFG)))
    replicated = NamedSharding(mesh, P())
    state = {"params": params,
             "opt_state": optax.sgd(1e-3).init(params),    # holds no array
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)}
    tokens = jax.ShapeDtypeStruct(
        (8, CFG.max_seq_len), jnp.int32,
        sharding=named_sharding(mesh, ("batch", "length")))
    return jax.jit(train_step, donate_argnums=0).lower(
        state, {"tokens": tokens}).compile().as_text()


def test_train_step_compiles_for_one_v5e_chip(v5e, as_on_chip):
    text = _compile_train_step(v5e[:1], MeshConfig(data=1))
    # flash forward, dq and dk/dv
    assert text.count("tpu_custom_call") >= 3


def test_train_step_compiles_under_a_v5e_mesh(v5e, as_on_chip):
    """A bare pallas_call under a multi-device jit fails to lower with
    "Mosaic kernels cannot be automatically partitioned"; the kernel must
    sit inside shard_map (ops.attention.mesh_flash_attention)."""
    text = _compile_train_step(v5e, MeshConfig(data=2, tensor=2))
    assert text.count("tpu_custom_call") >= 3


def test_paged_decode_kernel_compiles_for_v5e_gqa(v5e, as_on_chip):
    lanes, h, kh, d, bs, nb, mb = 8, 8, 2, 64, 16, 64, 8
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    text = jax.jit(paged_decode_attention).lower(
        arg((lanes, h, d), jnp.bfloat16),
        arg((nb, bs, kh, d), jnp.bfloat16),
        arg((nb, bs, kh, d), jnp.bfloat16),
        arg((lanes, mb), jnp.int32),
        arg((lanes,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_kernel_keeps_its_name_inside_a_layer_scan(v5e,
                                                                 as_on_chip):
    """In the engine's step the kernel sits in the layer scan, under no
    `jit` of its own; without `pallas_call(name=...)` its instruction is
    `closed_call.N`, and the device trace cannot tell it from any other
    Mosaic kernel (`benchmark/trace_reduce.py` names kernels by that)."""
    from ray_tpu.ops.attention import paged_attention
    lanes, h, d, bs, nb, mb, layers = 8, 8, 64, 16, 64, 8, 2
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def layers_of_attention(q, k_pools, v_pools, tables, ctx_lens):
        def layer(x, pools):
            out = paged_attention(x[:, None], *pools, tables, ctx_lens, None)
            return x + out[:, 0], None
        return jax.lax.scan(layer, q, (k_pools, v_pools))[0]

    text = jax.jit(layers_of_attention).lower(
        arg((lanes, h, d), jnp.bfloat16),
        arg((layers, nb, bs, h, d), jnp.bfloat16),
        arg((layers, nb, bs, h, d), jnp.bfloat16),
        arg((lanes, mb), jnp.int32),
        arg((lanes,), jnp.int32)).compile().as_text()
    kernels = [line.split(" = ")[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(
        k.lstrip("%").startswith("paged_decode_attention") for k in kernels)


def test_chip_binding_for_tpu_workers():
    chips = ChipAllocator(4)
    held = [chips.acquire(1) for _ in range(4)]
    assert sorted(held) == [(0,), (1,), (2,), (3,)]      # disjoint
    assert chips.acquire(1) is None                      # none left
    envs = [chip_env(c, 4) for c in held]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)

    # A worker's exit frees its chip for the next one.
    chips.release(held[2])
    assert chips.acquire(1) == (2,)

    # A lease of every chip is not confined at all.
    for c in held[:2] + [(2,), held[3]]:
        chips.release(c)
    assert chips.acquire(4) == (0, 1, 2, 3)
    assert chip_env((0, 1, 2, 3), 4) == {}

    # Whole hosts or single chips only.
    assert leasable(1, 4) and leasable(4, 4) and not leasable(2, 4)
    with pytest.raises(ValueError):
        chip_env((0, 1), 4)
    assert chip_env((0,), 1) == {}
