"""What can be checked about the chip without one.

libtpu compiles ahead of time for a v5e topology with no device present,
which is enough to catch a program XLA refuses to lower for real TPUs — the
interpreted kernels of the CPU suite lower to plain HLO and hide that.  AOT
says nothing about numerics, device ownership or the process model;
`chip_smoke.py` covers those on the chip.
"""

import collections
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private.accelerators import (
    ChipAllocator, chip_env, leasable)
from ray_tpu.inference.compiled import (count_pool_copies,
                                        count_select_sorts,
                                        count_weight_bytes_copied)
from ray_tpu.models import decoder, gpt
from ray_tpu.ops.attention import (kv_row_width, paged_blocks_per_step,
                                   paged_decode_attention, paged_rows_update)
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


def _compile_train_step(devices, mesh_cfg, cfg=CFG, batch=8):
    return _compiled_train_step(tuple(devices), mesh_cfg, cfg, batch)


@functools.lru_cache(maxsize=None)      # two tests read gpt2-small's step
def _compiled_train_step(devices, mesh_cfg, cfg, batch):
    mesh = create_mesh(mesh_cfg, devices=list(devices))
    _, train_step = gpt.make_train_step(
        cfg, optax.sgd(1e-3), mesh if len(devices) > 1 else None)

    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(lambda k: gpt.init_params(cfg, k), jax.random.key(0)),
        tree_shardings(mesh, gpt.param_specs(cfg)))
    replicated = NamedSharding(mesh, P())
    state = {"params": params,
             "opt_state": optax.sgd(1e-3).init(params),    # holds no array
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)}
    tokens = jax.ShapeDtypeStruct(
        (batch, cfg.max_seq_len), jnp.int32,
        sharding=named_sharding(mesh, ("batch", "length")))
    return jax.jit(train_step, donate_argnums=0).lower(
        state, {"tokens": tokens}).compile().as_text()


def test_train_step_compiles_for_one_v5e_chip(v5e, as_on_chip):
    text = _compile_train_step(v5e[:1], MeshConfig(data=1))
    # flash forward and backward (dq, dk and dv are one kernel)
    assert text.count("tpu_custom_call") >= 2


def test_flash_kernels_carry_the_name_the_trace_reader_keys_on(v5e,
                                                              as_on_chip):
    """`benchmark/readers.py::flash_roofline` reads the kernels whose
    instruction name, less its number, is `flash_attention`; a traced run
    of the train cell that lacks the metric is refused.  Every Mosaic call
    of the train step's layers is a flash kernel, so none there may carry
    another name; the loss head's are `logits_lse` and `loss_head_grads`,
    which `benchmark/layer_metrics/logits_lse_roofline.py` and
    `loss_head_grads_roofline.py` read by those names (the first sums every
    kernel whose name is `logits_lse`, so the second kernel's name is none
    that the reducer cuts down to that)."""
    from benchmark import trace_reduce
    text = _compile_train_step(v5e[:1], MeshConfig(data=1))
    counts = _kernel_counts(text)
    assert set(counts) == {"flash_attention", "logits_lse",
                           "loss_head_grads"}, counts
    assert counts["flash_attention"] == 2, counts
    # and as the reducer of a trace cuts an operation's text down to it
    assert {trace_reduce.describe(line.strip())[0]
            for line in text.splitlines()
            if trace_reduce.KERNEL_MARK in line} == set(counts)


_RESULT_ORDER = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\{([\d,]*)\S* ([\w\-]+)\(%([\w.\-]+)")


def _activation_relayouts(text, elements):
    """The `copy` and `transpose` instructions of the layer loop's body
    (fusions' insides apart) whose result has `elements` numbers in another
    order in memory than their operand, by the product they are made for
    (the tail of `op_name`).  A copy that keeps the order (a prefetch into
    `S(1)`) moves nothing around and is not counted."""
    order, found, fused = {}, collections.Counter(), False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.startswith("%fused_computation")
        m = _RESULT_ORDER.match(line)
        if m is None:
            continue
        name, dims, minor_to_major, op, operand = m.groups()
        order[name] = dims, minor_to_major
        if (fused or op not in ("copy", "transpose") or not dims
                or math.prod(map(int, dims.split(","))) != elements
                or "while/body" not in line
                or order.get(operand) == (dims, minor_to_major)):
            continue
        found[re.search(r'op_name="([^"]*)"', line).group(1).split(
            "closed_call/")[-1]] += 1
    return dict(found)


# (widths, mesh, chips, the batch, a chip's share of it, the relayouts of an
# activation a layer-step at most)
HEADS_LEFT_WHERE_THEY_ARE = {
    # `train_gpt2s_1chip`'s step
    "gpt2s_b24": (dict(d_model=768, n_heads=12, d_ff=3072),
                  MeshConfig(data=1), 1, 24, 24, 1),
    # 25 heads of 64: the thirteenth block of 128 columns is half a block
    "gpt2xl_width_b6": (dict(d_model=1600, n_heads=25, d_ff=6400),
                        MeshConfig(data=1), 1, 6, 6, 1),
    # the kernels under `shard_map`, the weights' gradients scattered
    "gpt2s_fsdp4": (dict(d_model=768, n_heads=12, d_ff=3072),
                    MeshConfig(fsdp=4), 4, 24, 6, 4),
}


@pytest.mark.parametrize("program", list(HEADS_LEFT_WHERE_THEY_ARE))
def test_flash_kernels_read_the_heads_where_the_train_step_leaves_them(
        v5e, as_on_chip, program):
    """A train step of one layer at 1,024 positions: the Mosaic calls take
    q, k, v, dO and O and give out, dq, dk and dv as `[b, 1024, h*d]`
    row-major, the heads side by side as the projections write and read
    them, and nothing between a projection and a kernel moves an
    activation.  Until PR 51 the kernels took `[b*h, 1024, 64]` and the
    step transposed seven activations a layer to it and four back (the
    trace's `copy bf16[24,12,1024,64]`, 7.85% of `train_gpt2s_1chip`'s
    step; eight standalone copies a layer in each of these programs).  XLA
    lays a `[b, 1024, h, 64]` value out with the length innermost (a head
    of 64 is half a tile's lanes), so a projection that comes out in four
    dimensions brings the copies back under another shape:
    `heads_attention` makes its products `[b, l, h*d]` wide.

    What is still moved, and held to no more: on one chip ONE activation a
    layer-step, the forward's out turned round for the gradient of `wo`
    (the kernel's result is row-major by constraint and the product
    contracts over batch and length; the parent's unfolding copy gave it
    either way).  Under a mesh (data, fsdp or tensor alike) the products
    for the gradients of `wq`, `wk` and `wv` take dq, dk and dv turned
    round as well: four, where the parent had eight, and seven before q, k
    and v crossed the shards' edge wide (`mesh_flash_attention`).  A later
    edit of `heads_attention` or of the kernels is held to this."""
    widths, mesh_cfg, chips, batch, share, most = HEADS_LEFT_WHERE_THEY_ARE[
        program]
    cfg = dataclasses.replace(CFG, vocab_size=50304, max_seq_len=1024,
                              remat=False, **widths)
    text = _compile_train_step(v5e[:chips], mesh_cfg, cfg, batch=batch)
    width = cfg.d_model
    wide, counts = f"bf16[{share},1024,{width}]", _kernel_counts(text)
    # the loss head's two kernels on one chip, once each in the body of the
    # chunks' loop (at gpt2-xl's width of twelve and a half blocks too);
    # under a mesh the head is `fused_cross_entropy_spmd`'s, plain XLA
    assert counts["flash_attention"] == 2 and set(counts) <= {
        "flash_attention", "logits_lse", "loss_head_grads"}, counts
    assert counts["logits_lse"] == counts["loss_head_grads"] == (
        1 if chips == 1 else 0), counts
    for line in text.splitlines():
        if ('custom_call_target="tpu_custom_call"' in line
                and "%flash_attention" in line.split(" = ")[0]):
            results, operands = line.split(" custom-call(")
            operands = operands.split("operand_layout_constraints=")[1].split(
                ", frontend_attributes")[0]
            # forward: out (and the logsumexp) of q, k, v; backward: dq, dk,
            # dv of q, k, v, dO, O (and the logsumexp), each row-major
            assert (results.count(wide), operands.count(wide + "{2,1,0}")
                    ) in ((1, 3), (3, 5)), line
        # no array in the shapes the kernels took or gave until PR 51
        assert not re.search(
            rf"\[{share},{cfg.n_heads},1024,64\]|"
            rf"\[{share * cfg.n_heads},1024,64\]", line), line
    moved = _activation_relayouts(text, share * 1024 * width)
    assert sum(moved.values()) <= most, moved
    assert any("hkd->bld" in made_for or "ed->bld" in made_for
               for made_for in moved), moved


@pytest.mark.parametrize("shape", ["mellum2_b2", "mellum2_b2_w1024",
                                   "llama3_8b_b2", "gemma2_9b_b2"])
def test_a_groups_flash_kernels_fit_the_cores_instruction_memory(v5e, shape):
    """The MoE train cell's calls, `[2,8192,32,128]` over 4 kv heads
    without and under its window, and two groups no cell runs (llama3-8b's
    32 over 8; 16 heads of 256 over 8): a grid step's heads are straight-line code, and a kernel compiled
    past 65,536 bundles ran at half its speed on the chip (PERF.md section
    6, PR 63: the forward of eight heads a step 17.8 ms where 7.6, the
    backward of four 35.3 where 14.8).  `_FLASH_FWD_PAIRS` and
    `_FLASH_BWD_PAIRS` hold both near half of that, a head of 256 columns
    counted twice (two a step compiled to 65.7 thousand backward); the
    count is `scripts/flash_bundles.py`'s, of the kernels compiled here."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ran = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "flash_bundles.py"),
         shape], cwd=root, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert ran.returncode == 0, ran.stdout[-2000:] + ran.stderr[-2000:]
    rows = json.loads(ran.stdout.splitlines()[-1])["rows"]
    assert len(rows) == 2, rows
    for row in rows:
        assert 10_000 < row["bundles"] < 40_000, row


def test_flash_kernels_compile_for_v5e_at_two_blocks_a_head_of_128(
        v5e, as_on_chip):
    """`flash_attention` and its gradients at `[4,2048,16,128]`, bf16,
    causal (`scripts/flash_step_time.py`'s `head128`; a head of 128 is a
    column block and 2,048 positions are two blocks of 1,024): the walk of
    the pair on the diagonal, the backward's crossed tiles of 512 as
    sub-tiles of 256, beside the walk of a pair under it, whole, each
    under its `pl.when`.  A sub-tile's slices (256 rows of bf16, 256-lane
    parts of the float32 `[128, q]` accumulator and `[1, q]` rows) are
    aligned to the chip's tiling or the compiler refuses them here; the
    three programs of the test above lower the sub-tiles of 128 that two
    heads of 64 a block take."""
    from ray_tpu.ops import attention as A
    arg = _arg_on(v5e[0])
    x = arg((4, 2048, 16 * 128), jnp.bfloat16)

    def step(q, k, v, g):
        def weighed(*wide):
            out = A.flash_attention(
                *(w.reshape(4, 2048, 16, 128) for w in wide), causal=True)
            return jnp.sum(out.reshape(g.shape).astype(jnp.float32)
                           * g.astype(jnp.float32))
        return jax.value_and_grad(weighed, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(x, x, x, x).compile().as_text()
    assert _kernel_counts(text) == {"flash_attention": 2}
    plan = A._flash_plan(*(jax.ShapeDtypeStruct((4, 2048, 16, 128),
                                                jnp.bfloat16),) * 2,
                         True, None, 1024, 1024, False)
    assert (plan.block_q, plan.block_k, plan.column_blocks) == (1024, 1024, 16)


def _computations(text):
    """{name: its lines} of a compiled module's computations."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split(" ")[1 if line.startswith("ENTRY") else 0]
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    return bodies


def _readers(bodies, lines, value):
    """The instructions among `lines` that take `value` (a name, `%` and
    all) as an operand, each with the text of the computation it calls, if
    it calls one."""
    found = []
    for line in lines:
        made, _, rest = line.partition(" = ")
        if re.search(re.escape(value) + r"[,)]", rest):
            called = re.search(r"calls=(%[\w.\-]+)", rest)
            found.append((made.strip(), "\n".join(
                bodies.get(called.group(1), [])) if called else rest))
    return found


def test_the_loss_head_makes_a_chunks_logits_once_and_reads_them_once(
        v5e, as_on_chip):
    """`train_gpt2s_1chip`'s step at one layer (b24 x 1024, vocabulary
    50,304; four chunks of 6,144 rows).  The chunks are a loop of four
    turns in the compiled step; its body holds one `logits_lse` call and no
    other product gives a chunk's `f32[6144,50304]`, so the backward
    recomputes none; and the chunk's logits have ONE reader, the
    `loss_head_grads` call that forms `softmax - onehot` once and gives dx
    and dhead.  Until PR 60 two XLA products read them, each forming it in
    its own prologue (5.69 ms a chunk where the kernel takes 4.97), and
    until PR 58 a third, `select_reduce_fusion`, read all 1.236 GB of them
    again for the sum of exponentials (PERF.md section 6).  dhead leaves
    the kernel `[V, D]`, as the embedding lies: nothing in the loop or in
    the entry computation turns a value of that size round (the parent's
    last dhead product came out `[D, V]` and a `copy f32[50304,768]` stood
    between it and the lookup's scatter-add)."""
    cfg = dataclasses.replace(CFG, vocab_size=50304, max_seq_len=1024,
                              remat=False, d_model=768, n_heads=12,
                              d_ff=3072)
    text = _compile_train_step(v5e[:1], MeshConfig(data=1), cfg, batch=24)
    counts = _kernel_counts(text)
    assert counts["logits_lse"] == counts["loss_head_grads"] == 1, counts
    wide = r"f32\[6144,50304\]"
    assert not re.search(rf" = {wide}\S* (convolution|dot)\(", text)
    bodies = _computations(text)
    entry = next(lines for name, lines in bodies.items()
                 if f"ENTRY {name}" in text)
    (chunk,) = [name for name, lines in bodies.items()
                if any(re.match(r"\s*%loss_head_grads[\w.\-]* = ", line)
                       for line in lines)]
    # the body of a loop of the entry computation that runs four times
    (loop,) = [line for line in entry if f"body={chunk}," in line]
    turns = "\n".join(bodies[re.search(r"condition=(%[\w.\-]+)",
                                       loop).group(1)])
    assert "constant(4)" in turns and "direction=LT" in turns, turns
    (value,) = re.findall(
        rf"(%[\w.\-]+) = {wide}\S* get-tuple-element\((?:\([^)]*\) )?"
        rf"%logits_lse[\w.\-]*\), index=0", "\n".join(bodies[chunk]))
    readers = _readers(bodies, bodies[chunk], value)
    assert len(readers) == 1, [made for made, _ in readers]
    assert "%loss_head_grads" in readers[0][0], readers
    turned = [line.strip()[:160] for line in entry + bodies[chunk]
              if re.search(r" = f32\[(50304,768|768,50304)\]\S* "
                           r"(copy|transpose)\(", line)]
    assert not turned, turned


def test_logits_lse_compiles_for_v5e_at_gpt2xls_width(v5e, as_on_chip):
    """gpt2-xl's head: D 1,600 is twelve and a half blocks of 128, so the
    blocks of `x` and of the head are the whole of D wide; a chunk of a
    b4 x 1024 step's rows.  `_lse_plan` takes the shape and the compiler
    takes the kernel (the fallback would be no `tpu_custom_call`)."""
    from ray_tpu.ops import cross_entropy as ce
    arg = _arg_on(v5e[0])
    assert ce._lse_plan(1024, 1600, 50304) == (1024, 384)
    text = jax.jit(ce.logits_lse).lower(
        arg((1024, 1600), jnp.bfloat16),
        arg((50304, 1600), jnp.bfloat16)).compile().as_text()
    assert _kernel_counts(text) == {"logits_lse": 1}


def test_loss_head_grads_compiles_for_v5e_at_gpt2xls_width(v5e, as_on_chip):
    """gpt2-xl's head under the gradients' kernel: `_grads_plan` takes D
    1,600 (the blocks of x, of the head and of dhead are the whole of D
    wide, dx's float32 sum pads a row to thirteen blocks of 128) and the
    compiler takes the kernel (the fallback would be no
    `tpu_custom_call`)."""
    from ray_tpu.ops import cross_entropy as ce
    arg = _arg_on(v5e[0])
    assert ce._grads_plan(1024, 1600, 50304) == (1024, 384)
    text = jax.jit(ce.loss_head_grads, donate_argnums=6).lower(
        arg((1024, 50304), jnp.float32), arg((1024,), jnp.float32),
        arg((1024,), jnp.int32), arg((1024,), jnp.float32),
        arg((1024, 1600), jnp.bfloat16), arg((50304, 1600), jnp.bfloat16),
        arg((50304, 1600), jnp.float32)).compile().as_text()
    assert _kernel_counts(text) == {"loss_head_grads": 1}


def test_train_step_compiles_under_a_v5e_mesh(v5e, as_on_chip):
    """A bare pallas_call under a multi-device jit fails to lower with
    "Mosaic kernels cannot be automatically partitioned"; the kernel must
    sit inside shard_map (ops.attention.mesh_flash_attention)."""
    text = _compile_train_step(v5e, MeshConfig(data=2, tensor=2))
    assert text.count("tpu_custom_call") >= 2


def _arg_on(device):
    dev = jax.sharding.SingleDeviceSharding(device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=dev)


def _pool_shape(layers, nb, bs, kh, d):
    return (layers, nb, bs, kv_row_width(kh, d))


def test_paged_decode_kernel_compiles_for_v5e_gqa(v5e, as_on_chip):
    lanes, h, kh, d, bs, nb, mb = 8, 8, 2, 64, 16, 64, 8
    arg = _arg_on(v5e[0])
    pool = arg(_pool_shape(2, nb, bs, kh, d), jnp.bfloat16)
    text = jax.jit(functools.partial(paged_decode_attention, kv_heads=kh)
                   ).lower(
        arg((lanes, h, d), jnp.bfloat16), pool, pool,
        arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
        arg((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,d", [(25, 64), (16, 128)],
                         ids=["gpt2xl_25x64_in_1664", "olmoe_16x128_in_2048"])
def test_paged_decode_kernel_compiles_for_v5e_at_the_serve_cells_shapes(
        v5e, as_on_chip, h, d):
    """`serve_gpt2xl_decode`'s and `serve_olmoe_decode`'s call: 16 lanes,
    64 blocks of 16 a lane, bf16.  One kernel, a lane a grid step: the
    sweep over the lane's context is inside it, run by run of R > 1 blocks
    and only over the runs that hold context, where until PR 32 every one
    of the lane's 64 blocks was a grid step (PERF.md section 6)."""
    lanes, bs, nb, mb = 16, 16, 512, 64
    arg = _arg_on(v5e[0])
    pool = arg(_pool_shape(2, nb, bs, h, d), jnp.bfloat16)
    args = (arg((lanes, h, d), jnp.bfloat16), pool, pool,
            arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
            arg((), jnp.int32))
    text = jax.jit(paged_decode_attention).lower(*args).compile().as_text()
    assert [k.split(".")[0] for k in _kernel_names(text)] == [
        "paged_decode_attention"]
    run = paged_blocks_per_step(bs, pool.shape[3], 2, mb)
    assert 1 < run <= mb and mb % run == 0
    # 2 pools x 2 buffers of a run's rows: a few MB of the 16 a core has
    assert 4 * run * bs * pool.shape[3] * 2 <= 4 * 2 ** 20
    # (the call is jitted: one level down)
    calls = _pallas_calls(jax.make_jaxpr(paged_decode_attention)(*args).jaxpr)
    assert len(calls) == 1
    steps_a_lane = math.prod(calls[0].params["grid_mapping"].grid) // lanes
    assert steps_a_lane <= mb // run


def _write_programs():
    """Every call of the write path a serve cell's step makes
    (`tests/test_paged_write.py::CELL_WRITES`, from the benchmark's own
    files) at its T=1 step and at its prefill program, and gpt2-xl's
    verify program of five rows a lane."""
    from tests.test_paged_write import CELL_WRITES
    for name, (lanes, (rows, chunk), pools) in CELL_WRITES.items():
        yield pytest.param(lanes, 1, pools, id=f"{name}_t1")
        yield pytest.param(rows, chunk, pools,
                           id=f"{name}_t{chunk}_{rows}_rows")
        if name.startswith("gpt2xl"):
            yield pytest.param(lanes, 5, pools, id=f"{name}_verify_t5")


@pytest.mark.parametrize("lanes,t,pools", _write_programs())
def test_rows_write_kernel_compiles_for_v5e_at_the_serve_cells_shapes(
        v5e, as_on_chip, lanes, t, pools):
    """The six serve cells' pools (bf16; two layers of each) and the rows
    of their T=1 step and of a prefill or verify program: ONE Mosaic call
    for all pools of a layer, the pools aliased through it (nothing
    allocated beside them), a group of 16 rows the unit whatever the
    block's size, and the lanes of a call walked a few at a time only
    where their groups would not fit (EvaByte's chunk of 512: 33 groups
    of 2 x 128 KB a lane)."""
    from ray_tpu.ops import paged_write
    arg = _arg_on(v5e[0])
    pools = tuple(arg((2, *p.shape[1:]), p.dtype) for p in pools)
    rows = tuple(arg((lanes, t, p.shape[3]), p.dtype) for p in pools)
    mb = pools[0].shape[1] // lanes
    args = (pools, rows, arg((lanes, mb), jnp.int32),
            arg((lanes, t), jnp.int32), arg((lanes, t), jnp.bool_),
            arg((), jnp.int32))
    assert paged_write.group_rows(pools, t) == 16
    compiled = jax.jit(paged_rows_update, donate_argnums=0).lower(
        *args).compile()
    assert _kernel_counts(compiled.as_text()) == {"paged_rows_write": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == sum(
        2 * math.prod(p.shape) for p in pools)
    assert memory.temp_size_in_bytes < 2 ** 20
    (call,) = _pallas_calls(jax.make_jaxpr(paged_rows_update)(*args).jaxpr)
    steps = math.prod(call.params["grid_mapping"].grid)
    assert steps == (4 if (lanes, t, pools[0].shape[3]) == (4, 512, 4096)
                     else 1)


def _kernel_names(text):
    return [line.split(" = ")[0].strip().lstrip("%")
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _kernel_counts(text):
    """How many Mosaic calls of each name (less its number) a program has."""
    return collections.Counter(k.split("%")[-1].split(".")[0]
                               for k in _kernel_names(text))


def _pool_block_updates(text, pool_shape):
    """`dynamic-update-slice`s whose result is the whole pool, fused or not:
    what the write path's XLA loop leaves in a program, a chain of them a
    layer (PERF.md section 6, PR 45), and the kernel does not."""
    made = re.compile(r" = \w+\[%s\]\S* dynamic-update-slice\("
                      % ",".join(str(d) for d in pool_shape))
    return [line for line in text.splitlines() if made.search(line)]


def _whole_contexts(text, lanes, mb, bs):
    """Result shapes that hold `mb * bs` rows for each of `lanes` lanes: a
    table gathered whole ([B, MB, BS, W], [B, max_ctx, KH, D]) or scored
    whole ([B, H, T, max_ctx]), which the masked-dense T > 1 path made for
    every lane a layer whatever the lanes held (PERF.md section 6, PR 38).
    The tiled path holds a tile of one lane."""
    found = set()
    for dims in re.findall(r" = \w+\[([\d,]+)\]", text):
        d = [int(x) for x in dims.split(",")]
        rows = mb * bs in d or any(d[i:i + 2] == [mb, bs]
                                   for i in range(len(d) - 1))
        if rows and lanes in d:
            found.add(dims)
    return found


@pytest.mark.parametrize("t,ctx_tile", [(32, None), (32, 128), (512, None)],
                         ids=["t32", "t32_asked_for_one_block", "t512"])
def test_a_tile_of_wide_rows_is_never_one_block_sliced_out(v5e, as_on_chip,
                                                          t, ctx_tile):
    """Rows of 4,096 bf16 columns in blocks of 128, EvaByte's: a context
    tile of ONE block is a lone `dynamic_slice` that XLA fuses into the
    product and re-lays the whole pool for (both pools copied: 9 GB at the
    cell's sizes, found on the chip in PR 38's sweep).  The tiled path
    takes two blocks at least there, whatever it is asked for, and the
    pools stay where they are."""
    from ray_tpu.ops.attention import paged_chunk_attention
    lanes, h, d, bs, nb, mb, layers = 4, 32, 128, 128, 64, 8, 2
    arg = _arg_on(v5e[0])
    pool = arg(_pool_shape(layers, nb, bs, h, d), jnp.bfloat16)

    def layers_of_attention(q, k_pool, v_pool, tables, ctx_lens, pos, valid):
        def layer(i, x):
            return x + paged_chunk_attention(x, k_pool, v_pool, tables,
                                             ctx_lens, pos, valid, i,
                                             ctx_tile=ctx_tile)
        return jax.lax.fori_loop(0, layers, layer, q)

    compiled = jax.jit(layers_of_attention).lower(
        arg((lanes, t, h, d), jnp.bfloat16), pool, pool,
        arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
        arg((lanes, t), jnp.int32), arg((lanes, t), jnp.bool_)).compile()
    assert count_pool_copies(compiled.as_text(), pool.shape) == 0
    assert "mini-gather" not in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < math.prod(pool.shape))                    # half a pool's bytes


def test_paged_decode_kernel_keeps_its_name_inside_a_layer_scan(v5e,
                                                                 as_on_chip):
    """In the engine's step the kernel sits in the layer scan, under no
    `jit` of its own; without `pallas_call(name=...)` its instruction is
    `closed_call.N`, and the device trace cannot tell it from any other
    Mosaic kernel (`benchmark/trace_reduce.py` names kernels by that)."""
    from ray_tpu.ops.attention import paged_attention
    lanes, h, d, bs, nb, mb, layers = 8, 8, 64, 16, 64, 8, 2
    arg = _arg_on(v5e[0])

    def layers_of_attention(q, k_pool, v_pool, tables, ctx_lens):
        def layer(x, i):
            out = paged_attention(x[:, None], k_pool, v_pool, tables,
                                  ctx_lens, None, i)
            return x + out[:, 0], None
        return jax.lax.scan(layer, q, jnp.arange(layers))[0]

    pool = arg(_pool_shape(layers, nb, bs, h, d), jnp.bfloat16)
    text = jax.jit(layers_of_attention).lower(
        arg((lanes, h, d), jnp.bfloat16), pool, pool,
        arg((lanes, mb), jnp.int32),
        arg((lanes,), jnp.int32)).compile().as_text()
    kernels = _kernel_names(text)
    assert kernels and all(
        k.startswith("paged_decode_attention") for k in kernels)


def _compile_engine_step(device, cfg, t, lanes=8, num_blocks=64,
                         block_size=16, model=gpt, max_seq_len=None,
                         prepared=True):
    """The engine's greedy step for `t` tokens a lane: no engine thread,
    no weights, the pool in the shape `PagedKVCache` stores, the
    parameters in the shapes of the tree the engine prepares
    (`model.serving_params`; with `prepared=False` those of `init_params`,
    which every tree before PR 28 served).  An expert configuration's
    step takes its load counters last."""
    from ray_tpu.inference.engine import InferenceEngine
    arg = _arg_on(device)
    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.key(0))
    if prepared:
        shapes = jax.eval_shape(lambda p: model.serving_params(p, cfg),
                                shapes)
    params = jax.tree.map(lambda x: arg(x.shape, x.dtype), shapes)
    pool = arg(_pool_shape(cfg.n_layers, num_blocks, block_size,
                           getattr(cfg, "n_kv_heads", cfg.n_heads),
                           cfg.head_dim), cfg.dtype)
    mb = (max_seq_len or cfg.max_seq_len) // block_size
    experts = getattr(cfg, "n_experts", 0)
    moe_load = (arg((experts + 2,), jnp.int32),) if experts else ()
    compiled = eng._make_step_fn(False).lower(
        params, pool, pool, arg((lanes, t), jnp.int32),
        arg((lanes, t), jnp.int32), arg((lanes, t), jnp.bool_),
        arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
        arg((lanes,), jnp.int32), arg((lanes,), jnp.float32),
        arg((lanes,), jnp.uint32), arg((lanes,), jnp.int32),
        *moe_load).compile()
    return compiled, pool, params


@pytest.mark.parametrize("t", [1, 32], ids=["t1", "t_prefill_chunk"])
@pytest.mark.parametrize("heads", [25, 12], ids=["25x64_padded_row",
                                                 "12x64"])
def test_engine_step_leaves_the_kv_pool_where_it_is(v5e, as_on_chip, heads,
                                                    t):
    """The mechanism of PERF.md section 6, PR 24, without a chip: the
    compiled step writes rows and reads blocks of the donated pools and
    never copies, slices out or stacks back the pool or a layer of it."""
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, d_model=heads * 64,
                        n_heads=heads, d_ff=256, max_seq_len=256,
                        scan_unroll=2)
    # (128 blocks: at four layers of these widths XLA prefetches a whole
    # stack of per-head projections into fast memory for the T=32 step's
    # loops, 15.6 MB of "scratch" that is not HBM: more than a pool of 64.)
    compiled, pool, params = _compile_engine_step(v5e[0], cfg, t,
                                                  num_blocks=128)
    text, memory = compiled.as_text(), compiled.memory_analysis()

    # By the program's own counter, and read off the text once more: no
    # copy, scatter or slice whose result is the pool or whole layers of it.
    assert count_pool_copies(text, pool.shape) == 0
    block_dims = ",".join(str(d) for d in pool.shape[1:])
    moved = re.compile(
        r" = \w+\[(?:\d+,)*%s\]\S* (copy|copy-start|scatter|dynamic-slice)\("
        % block_dims)
    assert not [line for line in text.splitlines() if moved.search(line)]
    # From parameter to result the pool has one layout: the row-major
    # one the kernel's DMA reads.
    layouts = set(re.findall(
        r"\[%d,%s\]\{([\d,]*(?::T[()\d,]*)?)" % (pool.shape[0], block_dims),
        text))
    # (untiled: the kernel's operand constraint, where there is a kernel)
    assert layouts - {"3,2,1,0"} == {"3,2,1,0:T(8,128)(2,1)"}

    pool_bytes = 2 * math.prod(pool.shape)                      # bf16
    assert memory.alias_size_in_bytes == 2 * pool_bytes         # K and V
    # Scratch: less than one pool; the weights are arguments as the step
    # multiplies them, not bf16 copies made here (PERF.md section 6, PR 28).
    assert memory.temp_size_in_bytes < pool_bytes

    # A layer body writes its rows with one kernel call at either T (the
    # loop trip holds `scan_unroll` bodies), and no whole-block update of
    # the pool is left behind it.
    assert _kernel_counts(text) == {
        "paged_rows_write": cfg.scan_unroll,
        **({"paged_decode_attention": cfg.scan_unroll} if t == 1 else {})}
    assert not _pool_block_updates(text, pool.shape)


def test_pool_update_counter_sees_the_block_write_loop(v5e, as_on_chip,
                                                       monkeypatch):
    """What every tree before PR 45 compiled, and what a block shape the
    kernel does not take still does: the XLA loop's whole-block updates of
    both pools.  The counter must not call that nothing."""
    from ray_tpu.ops import paged_write
    monkeypatch.setattr(paged_write, "group_rows", lambda pools, t: None)
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, d_model=12 * 64,
                        n_heads=12, d_ff=256, max_seq_len=256, scan_unroll=2)
    compiled, pool, _ = _compile_engine_step(v5e[0], cfg, 1, num_blocks=128)
    text = compiled.as_text()
    assert len(_pool_block_updates(text, pool.shape)) >= 2      # K and V
    assert "paged_rows_write" not in _kernel_counts(text)
    assert count_pool_copies(text, pool.shape) == 0


@pytest.mark.parametrize("t", [1, 32], ids=["t1", "t_prefill_chunk"])
def test_olmoe_step_reads_its_experts_where_they_are(v5e, as_on_chip, t):
    """OLMoE's widths (two of its sixteen layers; 16 lanes, 512 blocks,
    requests of 1024 at most, as `serve_olmoe_decode` runs it): the compiled
    step hands the stacked bf16 expert arrays to the grouped-matmul kernel
    as they are.  A per-layer slice, a bf16 copy of a float32 leaf or a
    transposed `w_down` would each be 0.27 GB moved per layer and step."""
    from ray_tpu.models import llama
    cfg = llama.LlamaConfig(
        vocab_size=50304, n_layers=2, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=1024, max_seq_len=4096, n_experts=64,
        n_experts_per_tok=8, qk_norm=True, param_dtype="bfloat16")
    compiled, pool, params = _compile_engine_step(
        v5e[0], cfg, t, lanes=16, num_blocks=512, model=llama,
        max_seq_len=1024)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}

    # Nothing but the parameters themselves (and the loop state that
    # carries them) has the shape of an expert array, of a layer's experts
    # or of one expert: no convert, copy, slice or transpose of them.
    made = re.compile(
        r" = \w+\[(?:\d+,)*(?:2048,1024|1024,2048)\]\S* ([\w\-]+)\(")
    moved = [m.group(1) for line in text.splitlines()
             if (m := made.search(line))]
    assert set(moved) <= {"parameter", "get-tuple-element"}, set(moved)
    assert count_pool_copies(text, pool.shape) == 0

    assert _kernel_counts(text) == {
        "moe_grouped_matmul": 3,                  # gate, up, down in the scan
        "paged_rows_write": 1,
        **({"paged_decode_attention": 1} if t == 1 else {})}
    assert not _pool_block_updates(text, pool.shape)
    # Scratch: a T=1 step needs next to none (2 MB at 16 layers); the T=32
    # step's is the activations of 512 rows (until PR 38 it gathered every
    # lane's context of 1024 for the masked-dense attention: 69 MB at 16
    # layers).  The parameters are the program's arguments: 2 layers are
    # 1.89 GB of the model's 13.84.
    assert memory.temp_size_in_bytes < (16 if t == 1 else 48) * 2 ** 20
    assert not _whole_contexts(text, 16, 1024 // 16, 16)
    assert memory.argument_size_in_bytes > sum(
        2 * math.prod(x.shape) for x in jax.tree.leaves(params))


# gpt2-xl's widths at the preset's four layer bodies a trip, the decode
# cell's 16 lanes and 512 blocks: eight of its 48 layers (two trips of its
# layer loop), and all of them (the cell's own programs).
XL8 = gpt.GPTConfig(n_layers=8, d_model=1600, n_heads=25, d_ff=6400,
                    scan_unroll=4)
_MATRIX = re.compile(
    r" = \w+\[(?:\d+,)*(?:1600,25,64|25,64,1600|1600,6400|6400,1600|"
    r"50304,1600|50304,1664|1024,1600|1024,1664)\]\S* ([\w\-]+)\(")
_RESULT = re.compile(r" = (.*?) [\w\-]+\(")     # an instruction's result type
_LAYER_MATRIX = re.compile(
    r"\w+\[((?:\d+,)?)(1600,25,64|25,64,1600|1600,6400|6400,1600)\]"
    r"\{([^}]*)\}")


def _layers_sliced_out(text, n_layers):
    """(dims, layout) of every array outside a fusion's body that has the
    shape of one layer, or of a group of fewer than `n_layers`, of a
    stacked matrix: what a layer loop slices out of its stacks and holds.
    (Inside a fusion's body such a shape is an operand being read.)"""
    found, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and " = " not in line:      # a computation
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and (made := _RESULT.search(line)):
            found += [(group + dims, layout) for group, dims, layout
                      in _LAYER_MATRIX.findall(made.group(1))
                      if int(group.rstrip(",") or 1) < n_layers]
    return found


def _whole_stack_prefetches(text, n_layers):
    """(line, layout) of every `copy-done` whose result is a whole stacked
    matrix of the layers: all `n_layers` of a leaf moved at once."""
    made = re.compile(r" = \w+\[%d,(?:1600,25,64|25,64,1600|1600,6400|"
                      r"6400,1600)\]\{([^}]*)\} copy-done\(" % n_layers)
    return [(line, m.group(1)) for line in text.splitlines()
            if (m := made.search(line))]


@pytest.mark.parametrize("t", [1, 32], ids=["t1", "t_prefill_chunk"])
@pytest.mark.parametrize("layers,unroll", [(8, 4), (8, 1), (48, 4)],
                         ids=["unroll_4", "unroll_1", "all_48_layers"])
def test_gpt2xl_step_multiplies_its_weights_as_they_are_held(v5e, as_on_chip,
                                                             layers, unroll,
                                                             t):
    """The mechanisms of PERF.md section 6, PRs 28 and 34, without a chip.
    On the tree the engine prepares, the compiled step has no `convert`,
    `copy` or `transpose` whose result has a matrix leaf's shape (61% of
    the decode cell's busy time went there: fp32 -> bf16 of every matrix,
    `w_down` and the table turned round, in every step), and next to no
    scratch where the raw tree's step held a bf16 copy of every weight.
    And the layer loop copies no group of layers out of the stacks: every
    layer indexes its own matrices (`decoder._layer_of`), the slice fuses
    into the product that reads it, and what XLA still slices out (two
    per-head projections a layer) it prefetches into fast memory, `S(1)`:
    their one read.  Until PR 34 the loop handed the stacks to `lax.scan`
    as `xs`, which at `unroll=4` slices groups of four layers out: every
    matrix written to HBM and read back, 38% of the cell's busy time.
    How many layer bodies a trip holds (`scan_unroll`) decides what the
    scheduler may overlap, not what is copied.  `all_48_layers`: the
    cell's own two programs, held to the same limits."""
    cfg = dataclasses.replace(XL8, n_layers=layers, scan_unroll=unroll)
    compiled, pool, params = _compile_engine_step(
        v5e[0], cfg, t, lanes=16, num_blocks=512)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert {x.dtype for x in jax.tree.leaves(params) if x.ndim > 2} == {
        jnp.dtype(jnp.bfloat16)}
    moved = {m.group(1) for line in text.splitlines()
             if (m := _MATRIX.search(line))}
    assert not moved & {"convert", "copy", "transpose"}, moved
    # Since the write path is a kernel (PR 45) the T=32 step of the EIGHT
    # layers at four bodies a trip prefetches one whole stack of a per-head
    # projection into fast memory in every trip (`copy-done
    # bf16[8,1600,25,64] S(1)`, 41 MB) where it prefetched pairs of layers
    # (`slice-done [2,1600,25,64] S(1)`).  The 48 layers of the cell leave
    # no room for that (246 MB) and its program has none: that one
    # instruction is an artefact of compiling a sixth of the model, so it
    # is counted apart, and everything else is held to what it was.
    prefill_of_4 = (t, unroll) == (32, 4)
    whole = _whole_stack_prefetches(text, cfg.n_layers)
    assert len(whole) == (1 if prefill_of_4 and layers == 8 else 0), whole
    assert all("S(1)" in layout for _, layout in whole), whole
    gone = {line for line, _ in whole}
    copied = count_weight_bytes_copied(
        "\n".join(x for x in text.splitlines() if x not in gone), params)
    assert not set(copied) & {"convert", "copy", "transpose", "remat"}, copied
    assert count_pool_copies(text, pool.shape) == 0
    # The bf16 matrices of eight layers are 0.49 GB, of 48 2.95.
    weights = sum(2 * math.prod(x.shape) for x in jax.tree.leaves(params)
                  if x.ndim > 2)
    sliced = sum(copied.get(op, 0)
                 for op in ("dynamic-slice", "slice", "copy-done"))
    # The T=32 step at four bodies a trip also prefetches more of those
    # two leaves (31% of the matrices' bytes in all at eight layers, 29% at
    # 48: 867 MB of 2,949) and keeps six of a trip's eight slices in HBM:
    # a twelfth of the bytes, where the group copies were all.
    assert sliced < weights // (3 if prefill_of_4 else 4), copied
    held = _layers_sliced_out(text, cfg.n_layers)
    assert held
    in_hbm = {dims for dims, layout in held if "S(1)" not in layout}
    assert in_hbm <= ({"1,1600,25,64"} if prefill_of_4 else set()), in_hbm
    # Scratch: activations.  Until PR 38 the T=32 step gathered every
    # lane's context of 1024 for the masked-dense attention, two layers of
    # K and V in flight (`bf16[16,1024,25,64]`, float32 scores
    # `[16,25,32,1024]`: 172 MB); now it holds a tile of one lane.
    assert memory.temp_size_in_bytes < (16 if t == 1 else 64) * 2 ** 20
    assert not _whole_contexts(text, 16, cfg.max_seq_len // 16, 16)
    assert _kernel_counts(text) == {
        "paged_rows_write": unroll,
        **({"paged_decode_attention": unroll} if t == 1 else {})}
    assert not _pool_block_updates(text, pool.shape)


def test_weight_copy_counter_sees_a_raw_float32_tree(v5e, as_on_chip):
    """What every tree before PR 28 compiled: `init_params`' float32
    leaves handed to the step, which rounds and turns them in every call.
    The counter must not call that nothing: every matrix is converted
    (bf16 bytes of all of them, `w_down` in its `copy`), the scratch holds
    the copies."""
    compiled, _, params = _compile_engine_step(
        v5e[0], XL8, 1, lanes=16, num_blocks=512, prepared=False)
    copied = count_weight_bytes_copied(compiled.as_text(), params)
    matrices = sum(2 * math.prod(x.shape) for x in jax.tree.leaves(params)
                   if x.ndim > 2)
    assert copied.get("convert", 0) + copied.get("copy", 0) >= matrices
    assert compiled.memory_analysis().temp_size_in_bytes > matrices // 2


def test_weight_copy_counter_sees_stacks_scanned_in_groups(v5e, as_on_chip):
    """What every tree before PR 34 compiled: the stacked matrices as the
    layer scan's `xs` at `unroll=4`, here gpt2-xl's feed-forward alone over
    eight layers.  `lax.scan` slices a group of four layers out of each
    stack a trip and XLA materialises it: the counter must read every
    matrix, and the scratch hold a group.  The form the served loops have
    (the stacks closed over, a layer indexed by the loop) copies nothing."""
    arg = _arg_on(v5e[0])
    stacks = {k: arg((8, 1600, 6400), jnp.bfloat16)
              for k in ("w_up", "w_down_t")}
    matrices = sum(2 * math.prod(x.shape) for x in stacks.values())

    def mlp(x, p):
        hidden = jax.nn.gelu(jnp.einsum("bd,df->bf", x, p["w_up"]))
        return x + jnp.einsum("bf,df->bd", hidden, p["w_down_t"])

    def stacks_as_xs(x, stacks):
        return jax.lax.scan(lambda x, p: (mlp(x, p), None), x, stacks,
                            unroll=4)[0]

    def stacks_indexed(x, stacks):
        return jax.lax.scan(
            lambda x, i: (mlp(x, decoder._layer_of(stacks, i)), None), x,
            jnp.arange(8), unroll=4)[0]

    def compiled(loop):
        return jax.jit(loop).lower(arg((16, 1600), jnp.bfloat16),
                                   stacks).compile()

    old, new = compiled(stacks_as_xs), compiled(stacks_indexed)
    copied = count_weight_bytes_copied(old.as_text(), stacks)
    assert copied.get("dynamic-slice", 0) + copied.get("slice", 0) \
        >= matrices, copied
    assert old.memory_analysis().temp_size_in_bytes > matrices // 8
    assert not count_weight_bytes_copied(new.as_text(), stacks)
    assert new.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_pool_copy_counter_sees_a_pool_scanned_over_layers(v5e, as_on_chip):
    """What every tree before PR 24 compiled: the pools as the layer
    scan's xs and stacked outputs.  The counter must not call that 0."""
    from ray_tpu.ops.attention import paged_attention
    lanes, h, d, bs, nb, mb, layers = 8, 12, 64, 16, 64, 8, 4
    arg = _arg_on(v5e[0])

    def scanned(q, k_pool, v_pool, tables, ctx_lens):
        def layer(x, pools):
            k_l, v_l = (p[None].at[0, tables[:, 0], 0, :64].set(
                x[:, 0].astype(p.dtype)) for p in pools)
            out = paged_attention(x[:, None], k_l, v_l, tables, ctx_lens,
                                  None)
            return x + out[:, 0], (k_l[0], v_l[0])
        return jax.lax.scan(layer, q, (k_pool, v_pool))

    pool = arg(_pool_shape(layers, nb, bs, h, d), jnp.bfloat16)
    text = jax.jit(scanned, donate_argnums=(1, 2)).lower(
        arg((lanes, h, d), jnp.bfloat16), pool, pool,
        arg((lanes, mb), jnp.int32),
        arg((lanes,), jnp.int32)).compile().as_text()
    assert count_pool_copies(text, pool.shape) > 0


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


# A.X-K1 at its published widths as `serve_axk1_docs_decode` serves it: one
# chip's share (12 of 192 routed experts a layer, an eighth of the
# vocabulary), one dense + six expert layers, 32 lanes over a latent pool of
# 196,608 tokens in blocks of 128, requests of 16,896 tokens at most, the
# prefill program over 4 lanes x 512.
def _axk1_cell():
    from ray_tpu.models import axk1
    cfg = axk1.Axk1Config(vocab_size=20480, n_layers=7, n_experts_held=12,
                          max_seq_len=131072)
    return axk1, cfg, dict(lanes=32, block_size=128, num_blocks=1536,
                           max_seq_len=16896)


def _compile_axk1_step(device, t, rows):
    """The engine's greedy step over a latent pool: `rows` lanes of `t`
    tokens; compact (gathered by lane index) where rows < lanes."""
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.ops.attention import latent_row_width
    model, cfg, cell = _axk1_cell()
    arg = _arg_on(device)
    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.key(0))
    given = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves(shapes))
    shapes = jax.eval_shape(lambda p: model.serving_params(p, cfg), shapes)
    served = sum(math.prod(x.shape) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    params = jax.tree.map(lambda x: arg(x.shape, x.dtype), shapes)
    pool = arg((cfg.n_layers, cell["num_blocks"], cell["block_size"],
                latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)),
               cfg.dtype)
    lanes = cell["lanes"]
    compact = rows < lanes
    mb = cell["max_seq_len"] // cell["block_size"]
    compiled = eng._make_step_fn(False, False, compact).lower(
        params, pool, None, arg((rows, t), jnp.int32),
        arg((rows, t), jnp.int32), arg((rows, t), jnp.bool_),
        arg((lanes, mb), jnp.int32), arg((rows,), jnp.int32),
        arg((rows,), jnp.int32), arg((rows,), jnp.float32),
        arg((rows,), jnp.uint32), arg((rows,), jnp.int32),
        *((arg((rows,), jnp.int32),) if compact else ()),
        arg((lanes,), jnp.int32),
        arg((cfg.n_experts_held + 2,), jnp.int32)).compile()
    return compiled, pool, params, served - given


def test_latent_decode_kernel_compiles_for_v5e_at_every_block_size(
        v5e, as_on_chip):
    from ray_tpu.ops.attention import latent_decode_attention
    arg = _arg_on(v5e[0])
    for bs in (16, 32, 64, 128):
        text = jax.jit(functools.partial(
            latent_decode_attention, v_width=512, scale=0.13)).lower(
            arg((8, 64, 640), jnp.bfloat16),
            arg((2, 4096 // bs, bs, 640), jnp.bfloat16),
            arg((8, 2048 // bs), jnp.int32), arg((8,), jnp.int32),
            arg((), jnp.int32)).compile().as_text()
        (kernel,) = _kernel_names(text)
        assert kernel.split("%")[-1].startswith("latent_decode_attention")


def _latent_walk(name, lanes, heads, pool, mb):
    """`latent_decode_attention` under `name`: (call, argument shapes, the
    pool's place among them)."""
    from ray_tpu.ops.attention import latent_decode_attention
    return (functools.partial(latent_decode_attention, v_width=512,
                              scale=0.13, name=name),
            [((lanes, heads, pool[3]), jnp.bfloat16), (pool, jnp.bfloat16),
             ((lanes, mb), jnp.int32), ((lanes,), jnp.int32),
             ((), jnp.int32)], 1)


def _window_walk(name, lanes, pool, mb):
    from ray_tpu.ops.attention import window_latent_decode_attention
    return (functools.partial(window_latent_decode_attention, v_width=1024,
                              scale=0.13, span=513, name=name),
            [((lanes, 64, pool[3]), jnp.bfloat16), (pool, jnp.bfloat16),
             ((lanes, mb), jnp.int32), ((lanes,), jnp.int32),
             ((lanes,), jnp.int32), ((), jnp.int32)], 1)


def _index_walk(name, lanes, pool, mb):
    from ray_tpu.ops.attention import sparse_index_scores
    return (functools.partial(sparse_index_scores, name=name),
            [((lanes, 64, 128), jnp.bfloat16), ((lanes, 64), jnp.float32),
             (pool, jnp.bfloat16), ((lanes, mb), jnp.int32),
             ((lanes,), jnp.int32), ((), jnp.int32)], 2)


def _pallas_calls(jaxpr):
    """Every `pallas_call` of a jaxpr, those of the jitted calls inside it
    too."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found.append(e)
        elif e.primitive.name in ("jit", "pjit"):
            found += _pallas_calls(e.params["jaxpr"].jaxpr)
    return found


# The single-query kernels over one pool of rows at the two cells' shapes:
# A.X-K1's T=1 step (32 lanes, 64 heads, rows of 640, a table of 132 blocks
# of 128 over a pool of 7 layers); dots3's (64 lanes, and 64 rows a trip of
# a chunk): the indexed attention over 2,048 gathered rows a lane as a pool
# of one layer (128 heads), the window layers' rows of 1,152, the index
# keys of 128 under 64 index heads, each over a table of 133.
_ROW_WALKS = {
    "latent_decode_attention": lambda n: _latent_walk(
        n, 32, 64, (7, 1536, 128, 640), 132),
    "sparse_latent_decode_attention": lambda n: _latent_walk(
        n, 64, 128, (1, 1024, 128, 640), 16),
    "sparse_latent_chunk_attention": lambda n: _latent_walk(
        n, 64, 128, (1, 1024, 128, 640), 16),
    "window_latent_decode_attention": lambda n: _window_walk(
        n, 64, (6, 768, 128, 1152), 133),
    "window_latent_chunk_attention": lambda n: _window_walk(
        n, 64, (6, 768, 128, 1152), 133),
    "sparse_index_scores": lambda n: _index_walk(
        n, 64, (3, 1536, 128, 128), 133),
    "sparse_index_chunk_scores": lambda n: _index_walk(
        n, 64, (3, 1536, 128, 128), 133),
}


@pytest.mark.parametrize("name", list(_ROW_WALKS))
def test_row_walk_kernels_compile_for_v5e_at_the_cells_shapes(
        v5e, as_on_chip, name):
    """One Mosaic call under the name the benchmark's readers find it by,
    a lane a grid step (the walk over the lane's context is inside it, as
    the paged kernel's since PR 32; until PR 49 a run of 512-1,024 rows
    was a grid step: 33 a lane at A.X-K1's table), the pool handed in
    whole and left where it is."""
    fn, shapes, pool_at = _ROW_WALKS[name](name)
    arg = _arg_on(v5e[0])
    args = [arg(*s) for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert [k.split("%")[-1].split(".")[0] for k in _kernel_names(text)] == [
        name]
    assert count_pool_copies(text, shapes[pool_at][0]) == 0
    (call,) = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    assert call.params["grid_mapping"].grid == (shapes[0][0][0],)


@pytest.mark.parametrize("bs", [16, 32, 64, 128])
def test_window_and_index_walks_compile_for_v5e_at_every_block_size(
        v5e, as_on_chip, bs):
    """Blocks narrower than the lane width: a run's rows stack all the
    same (a block is whole tile rows), and a run of index keys is whole
    lane widths of scores, so its store into the lane's row is aligned."""
    for walk, width in ((_window_walk, 1152), (_index_walk, 128)):
        for mb in (2048 // bs, 6):
            fn, shapes, _ = walk("k", 8, (2, 4096 // bs, bs, width), mb)
            arg = _arg_on(v5e[0])
            text = jax.jit(fn).lower(
                *(arg(*s) for s in shapes)).compile().as_text()
            assert len(_kernel_names(text)) == 1


def test_select_sort_counter_sees_a_sort_of_every_lanes_scores(v5e,
                                                              as_on_chip):
    """What every tree from PR 41 to PR 47 compiled for the choice: a stable
    sort of all of a lane's scores, each carrying its row's place.  The
    counter must not call that 0, nor count the choice as it is now, a
    sort of one row (the expert dispatch's) or a narrower one (a router's
    top-k over 256 experts, which the cell's step holds).  At 8 lanes over a
    table of 8 blocks, the best 128 of 1,024 scores: the counter reads the
    compiler's text, and a sort of the cell's 17,024 scores takes the
    compiler 38 s however few the lanes (PR 59: 158.7 s of the suite)."""
    from ray_tpu.ops.attention import sparse_select
    arg = _arg_on(v5e[0])
    lanes, mb, bs, k = 8, 8, 128, 128

    def sorted_choice(scores, tables):
        place = (jnp.repeat(tables, bs, axis=1) * bs
                 + jnp.arange(mb * bs, dtype=jnp.int32) % bs)
        return jax.lax.sort((-scores, place), dimension=1, num_keys=1,
                            is_stable=True)[1][:, :k]

    def one_row(scores, tables):
        return (jnp.argsort(scores.reshape(-1))[:k] + tables[0, 0]
                + jax.lax.top_k(scores[:, :256], 8)[1][0, 0])

    def text_of(fn):
        return jax.jit(fn).lower(arg((lanes, mb * bs), jnp.float32),
                                 arg((lanes, mb), jnp.int32)
                                 ).compile().as_text()

    assert count_select_sorts(text_of(sorted_choice), mb * bs) == 1
    assert count_select_sorts(text_of(one_row), mb * bs) == 0
    chosen = text_of(functools.partial(sparse_select, block_size=bs, k=k))
    assert count_select_sorts(chosen, mb * bs) == 0
    assert _kernel_counts(chosen) == {"sparse_select": 1}


def _kv_walk(windowed, lanes=64, mb=133):
    """The single-query kernels over a K and a V pool at Trinity-Mini's
    cell: 32 query heads over 4 key/value heads of 128 (rows of 512), the
    full layers' pools of 2 layers and 1,536 blocks of 128, the window
    layers' of 6 layers and 768 blocks, a table of 133."""
    from ray_tpu.ops.attention import window_paged_decode_attention
    pool = (6, 768, 128, 512) if windowed else (2, 1536, 128, 512)
    shapes = [((lanes, 32, 128), jnp.bfloat16), (pool, jnp.bfloat16),
              (pool, jnp.bfloat16), ((lanes, mb), jnp.int32),
              ((lanes,), jnp.int32)]
    if not windowed:
        return (functools.partial(paged_decode_attention, kv_heads=4),
                shapes + [((), jnp.int32)], pool)
    return (functools.partial(window_paged_decode_attention, span=2048,
                              kv_heads=4),
            shapes + [((lanes,), jnp.int32), ((), jnp.int32)], pool)


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_trinity_decode_kernels_compile_for_v5e_under_names_of_their_own(
        v5e, as_on_chip, windowed):
    """One Mosaic call, a lane a grid step, both pools handed in whole and
    left where they are; the window layers' kernel under a name of its own
    (`paged_decode_attention` stays the full layers' alone, so a trace
    tells the two apart), its runs two of 9 blocks for the 17 a window of
    2,048 touches."""
    from ray_tpu.ops.attention import window_blocks_per_step
    fn, shapes, pool = _kv_walk(windowed)
    arg = _arg_on(v5e[0])
    args = [arg(*s) for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert [k.split("%")[-1].split(".")[0] for k in _kernel_names(text)] == [
        "window_paged_decode_attention" if windowed
        else "paged_decode_attention"]
    assert count_pool_copies(text, pool) == 0
    (call,) = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    assert call.params["grid_mapping"].grid == (64,)
    assert window_blocks_per_step(128, 512, 2, 17) == 9
    assert paged_blocks_per_step(128, 512, 2, 133) == 8


@pytest.mark.parametrize("t,rows,pair", [
    (1, 8, False), (32, 8, False), (32, 2, False), (32, 1, False),
    (32, 2, True), (32, 1, True)],
    ids=["t1", "t_prefill_chunk", "compact_2_rows", "compact_1_row",
         "pair_2_rows", "pair_1_row"])
def test_the_engines_entry_is_the_step_behind_a_few_slices(v5e, as_on_chip,
                                                           t, rows, pair):
    """PR 42: the program the engine runs takes a population's lane arrays
    as ONE int32 buffer [rows, 3 T + 5 (+ 1: a compact program's `rows`)]
    and unpacks it in front of the step `_make_step_fn` gives (which the
    tests above and the benchmark's tools lower by itself).  Compiled for
    the chip it is that step: the pools donated through the outer call and
    left where they are, the same kernels, no scratch to speak of beyond
    the step's.  The pair's entry (PR 53) likewise: ONE flat buffer, the
    decoding lanes' [lanes, 8] and behind it the chunk's [rows, 3 T + 6],
    where its step has seventeen arrays."""
    from ray_tpu.inference.engine import InferenceEngine
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, d_model=25 * 64,
                        n_heads=25, d_ff=256, max_seq_len=256, scan_unroll=2)
    lanes, num_blocks, block_size = 8, 128, 16
    compact = rows < lanes
    arg = _arg_on(v5e[0])
    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = gpt, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    eng._pairs, eng.max_lanes = pair, lanes
    params = jax.tree.map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: gpt.serving_params(
            gpt.init_params(cfg, k), cfg), jax.random.key(0)))
    pool = arg(_pool_shape(cfg.n_layers, num_blocks, block_size, cfg.n_heads,
                           cfg.head_dim), cfg.dtype)
    tables = arg((lanes, cfg.max_seq_len // block_size), jnp.int32)
    last_tok = arg((lanes,), jnp.int32)

    def population(rows, t, compact):
        return (arg((rows, t), jnp.int32), arg((rows, t), jnp.int32),
                arg((rows, t), jnp.bool_), arg((rows,), jnp.int32),
                arg((rows,), jnp.int32), arg((rows,), jnp.float32),
                arg((rows,), jnp.uint32), arg((rows,), jnp.int32),
                *((arg((rows,), jnp.int32),) if compact else ()))

    if pair:
        step = eng._make_step_fn(False, False, True, True).lower(
            params, pool, pool, population(lanes, 1, False),
            population(rows, t, True), tables, last_tok).compile()
        buffer = arg((lanes * 8 + rows * (3 * t + 6),), jnp.int32)
    else:
        mine = population(rows, t, compact)
        step = eng._make_step_fn(False, False, compact).lower(
            params, pool, pool, *mine[:3], tables, *mine[3:],
            last_tok).compile()
        buffer = arg((rows, 3 * t + 5 + compact), jnp.int32)
    entry = eng._make_entry(t, False, False, rows if compact else 0).lower(
        params, pool, pool, buffer, tables, last_tok).compile()
    text, memory = entry.as_text(), entry.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape)
    assert memory.alias_size_in_bytes == 2 * pool_bytes \
        == step.memory_analysis().alias_size_in_bytes
    assert count_pool_copies(text, pool.shape) == 0
    assert _kernel_names(text) == _kernel_names(step.as_text())
    if pair:        # both populations' kernels in the one program
        assert {"paged_decode_attention", "paged_rows_write"} <= set(
            _kernel_counts(text))
    scratch = step.memory_analysis().temp_size_in_bytes
    assert abs(memory.temp_size_in_bytes - scratch) < 256 * 1024    # of MBs
    # one lane argument where the step has eight or nine, or a pair's two
    # populations (of eight and nine)
    assert len(entry.input_shardings[0]) == len(step.input_shardings[0]) \
        - (1 if pair else 8 if compact else 7)


# The serve cells, and the pair's program each is compiled at here: its
# widest where its chunk is short, the form of a question behind a cached
# document (a quarter of the chunk, one row) where its chunk is long.
_PAIRS = {
    "serve_gpt2xl_decode": (32, 4), "serve_olmoe_decode": (32, 4),
    "serve_axk1_docs_decode": (128, 1),
    "serve_evabyte_sessions_decode": (128, 1),
    "serve_dots3_docs_decode": (128, 1),
    "serve_falconh1_chat_decode": (64, 1),
    "serve_nemotron3_agents_decode": (64, 1),
    "serve_trinity_docs_decode": (128, 1),
    "serve_lfm2_rag_decode": (64, 1),
    "serve_kimilinear_reasoning_decode": (64, 1)}


# Bytes of weights the pair's program may copy over its cell's T=1 program's
# (a MiB anywhere else): what XLA re-lays for the chunk's products, once a
# step, as the prefill programs the pair replaces did (ROADMAP.md S18's
# kind; PERF.md section 7).  Falcon-H1: one of `wk` / `wv`, the whole
# [9, 5120, 512] stack (47.2 MB of 12.7 GB read); Nemotron-3: a
# [2688, 2816] matrix converted (15.1 MB of 8.7 GB).
_PAIR_COPIES = {"serve_falconh1_chat_decode": 48 * 2 ** 20,
                "serve_nemotron3_agents_decode": 16 * 2 ** 20,
                # LFM2: a [2048, 512] `wk` or `wv` converted for the
                # chunk's product (2 MB of 12.8 GB)
                "serve_lfm2_rag_decode": 3 * 2 ** 20}


def _cell_engine(cell, device):
    """(an engine's programs without an engine, the served weights, the
    pools and state, the block tables) of a serve cell, as shapes on
    `device`, from the files the benchmark runs the cell from."""
    import importlib

    from benchmark import manifest
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.inference.kv_cache import PagedKVCache
    m = manifest.load()
    file = m.load_config(m.cells[cell]["config"])
    cfg = manifest.model_config(file, None)
    traffic = m.load_traffic(m.cells[cell]["traffic"])["engine"]
    model = importlib.import_module(file["module"])
    arg = _arg_on(device)
    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls, eng._pairs = "tpu", {}, True
    eng.max_lanes = traffic["max_lanes"]
    seen = {}

    def pools():
        cache = PagedKVCache.for_model(
            model, cfg, num_blocks=traffic["num_blocks"],
            block_size=traffic["block_size"], max_lanes=eng.max_lanes,
            max_seq_len=traffic.get("max_seq_len", cfg.max_seq_len),
            ahead=2 * traffic["prefill_chunk"])
        seen["tables"] = cache.block_tables.shape
        return cache.step_pools

    def on_device(tree):
        return jax.tree.map(lambda x: arg(x.shape, x.dtype), tree)

    k, v = on_device(jax.eval_shape(pools))
    params = on_device(jax.eval_shape(
        lambda key: model.serving_params(model.init_params(cfg, key), cfg),
        jax.random.key(0)))
    held = getattr(cfg, "n_experts_held", 0) or getattr(cfg, "n_experts", 0)
    carried = (arg((eng.max_lanes,), jnp.int32),
               *((arg((held + 2,), jnp.int32),) if held else ()))
    return eng, params, (k, v), arg(seen["tables"], jnp.int32), carried


# (the cells whose two programs take one to three minutes to compile here,
# and the one whose programs another test compiles, are `slow`: tier-1 has
# a time limit; the four others are the claimed
# cells' K/V programs with and without experts, a state cache's and a
# windowed one's)
@pytest.mark.parametrize("cell", [
    pytest.param(cell, marks=pytest.mark.slow) if cell in (
        "serve_axk1_docs_decode", "serve_dots3_docs_decode",
        "serve_nemotron3_agents_decode", "serve_trinity_docs_decode",
        # (its T=1 and widest pair's programs have a test of their own)
        "serve_kimilinear_reasoning_decode")
    else cell for cell in _PAIRS])
def test_the_pairs_program_fits_a_v5e_and_reads_weights_and_pools_in_place(
        v5e, as_on_chip, cell):
    """PR 53: the program of an iteration that admits, the prefilling
    lanes' [rows, T] beside the decoding lanes' [max_lanes, 1], compiled for
    the chip at each serve cell's own sizes beside the cell's T=1 program:
    arguments and temporaries under the compiler's 15.75 GB, every pool and
    state buffer donated and left where it is, no more bytes of weights
    copied than the T=1 program copies (but `_PAIR_COPIES`), and the
    kernels of BOTH populations
    in one program: the T=1 program's own and a chunk's."""
    t, rows = _PAIRS[cell]
    eng, params, pools, tables, carried = _cell_engine(cell, v5e[0])
    arg, lanes = _arg_on(v5e[0]), eng.max_lanes
    one = eng._make_entry(1, False, False, 0).lower(
        params, *pools, arg((lanes, 8), jnp.int32), tables,
        *carried).compile()
    pair = eng._make_entry(t, False, False, rows).lower(
        params, *pools, arg((lanes * 8 + rows * (3 * t + 6),), jnp.int32),
        tables, *carried).compile()
    text, memory = pair.as_text(), pair.memory_analysis()
    held = jax.tree.leaves(pools)
    # (a state's tail buffer is padded to the chip's tiles: no less)
    assert memory.alias_size_in_bytes \
        == one.memory_analysis().alias_size_in_bytes >= sum(
            math.prod(p.shape) * p.dtype.itemsize for p in held)
    assert memory.temp_size_in_bytes < 11.4e9
    assert memory.argument_size_in_bytes \
        - one.memory_analysis().argument_size_in_bytes < 2 ** 20
    for p in held:                      # rows in blocks, and a state's slots
        assert count_pool_copies(text, p.shape) == 0, p.shape
    # (`copy-done`: XLA's own prefetch of a layer's slice, which reads it
    # once in the product's place)
    copied, alone = ({k: v for k, v in count_weight_bytes_copied(
        x, params).items() if k != "copy-done"}
        for x in (text, one.as_text()))
    assert sum(copied.values()) <= sum(alone.values()) + _PAIR_COPIES.get(
        cell, 2 ** 20), (copied, alone)
    # the decoding lanes' kernels are all there, under their names
    assert set(_kernel_counts(one.as_text())) <= set(_kernel_counts(text))
    # the last tokens of every lane come back, the sampled rows of both
    assert f"s32[{lanes + rows}]" in text


# ---------------------------------------------------------------------------
# The serve cells' programs at the cells' own sizes: ONE check, a row a
# (cell, program).  The next configuration's is a row.
# ---------------------------------------------------------------------------

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
MIB = 2 ** 20


@dataclasses.dataclass(frozen=True)
class CellProgram:
    """One program of a serve cell as the chip must take it."""
    cell: str
    name: str                       # the case's id behind the cell's
    source: object                  # (row, device) -> text, memory, pools,
    #                                 params (None: the source has none)
    t: int                          # tokens a row
    rows: int                       # rows of `t` (a pair's: beside the lanes)
    pools: tuple                    # ((shape, dtype), ...) as the step takes
    arguments: tuple                # (over, under) bytes of arguments
    kernels: dict                   # Mosaic calls by name: a count, or None
    #                                 (there, however often)
    which: str = ""                 # a tool's own name for the program
    lanes: int = 0                  # the engine's lanes, where the row says
    padded: bool = False            # a tails buffer's slots are padded to
    #                                 whole tiles: alias up to 0.2% over
    snapshots: int = 0              # bytes of a state cache's snapshot pool
    temps: float = 0                # temporaries under this (0: as they fit)
    row_pools: int = None           # the leading pools that hold rows in
    #                                 blocks: no block read and written back
    #                                 for a row (None: every pool)
    weights: tuple = ()             # (over, under) bytes of served weights
    all_bf16: bool = False          # every served leaf
    forbidden: tuple = ()           # kinds of weight copies there are none of
    copied_most: tuple = ()         # ((kinds, bytes), ...): at most, summed
    extra: object = None            # (text, memory, pools, row): what one
    #                                 family alone asserts


def _from_tool(tool):
    """A cell's program from `benchmark/tools/<tool>.main(cell, which)`
    (from the cell's configuration and traffic files)."""
    def source(row, device):
        import importlib
        try:
            texts = importlib.import_module(
                f"benchmark.tools.{tool}").main(row.cell, row.which)
        except RuntimeError as e:       # no v5e topology can be described
            pytest.skip(str(e))
        return (*texts[(row.t, row.rows)], None)
    return source


def _from_entry(row, device):
    """The engine's own entry (`_make_entry`), compiled from the files the
    benchmark runs the cell from: the T=1 step, or `rows` x `t` beside it."""
    eng, params, pools, tables, carried = _cell_engine(row.cell, device)
    arg, lanes = _arg_on(device), eng.max_lanes
    assert lanes == row.lanes
    lane_ints = (lanes, 8) if row.t == 1 else (
        lanes * 8 + row.rows * (3 * row.t + 6),)
    compiled = eng._make_entry(
        row.t, False, False, 0 if row.t == 1 else row.rows).lower(
        params, *pools, arg(lane_ints, jnp.int32), tables,
        *carried).compile()
    return (compiled.as_text(), compiled.memory_analysis(),
            jax.tree.leaves(pools), params)


def _from_axk1_step(row, device):
    compiled, pool, params, extra = _compile_axk1_step(device, row.t,
                                                       row.rows)
    assert extra == 0                     # served_bytes - given_bytes
    return compiled.as_text(), compiled.memory_analysis(), [pool], params


@functools.lru_cache(maxsize=None)      # (texts, not programs: six at once)
def _evabyte_programs(device):
    from benchmark.tools import aot_evabyte_sizes
    return {name.split(" of 24")[0].split(" 16 ->")[0]: (
        compiled.as_text(), compiled.memory_analysis(),
        [jax.ShapeDtypeStruct(pool, BF16)] * 2, params)
        for name, compiled, pool, params
        in aot_evabyte_sizes.programs(device)}


def _from_evabyte_tool(row, device):
    return _evabyte_programs(device)[
        row.which or f"engine step T={row.t} rows={row.rows}"]


_NO_WEIGHT_MOVED = dict(forbidden=("copy", "transpose", "remat"),
                        copied_most=((("convert",), MIB),))

# A.X-K1 at its published widths as `serve_axk1_docs_decode` serves it (the
# engine's greedy step, `_compile_axk1_step`): the latent pool donated and
# left where it is, no matrix converted, copied or transposed in a step (the
# absorbed halves of the up-projection are arguments, made once;
# `copy-done` is XLA's own prefetch of a layer's slice, `convert` the norm
# scales, kilobytes), the T=1 step on the latent kernel and the grouped
# multiply, the chunk on the grouped multiply alone; one write call a layer
# body, the lead layer's and the scan's.
_AXK1 = CellProgram(
    "serve_axk1_docs_decode", "t1_32_lanes", _from_axk1_step, 1, 32,
    pools=(((7, 1536, 128, 640), BF16),), arguments=(11.0e9, 11.6e9),
    kernels={"paged_rows_write": 2, "moe_grouped_matmul": None,
             "latent_decode_attention": None},
    temps=64 * MIB, all_bf16=True, **_NO_WEIGHT_MOVED)
_AXK1_CHUNK = dict(
    kernels={"paged_rows_write": 2, "moe_grouped_matmul": None},
    temps=1024 * MIB)


# EvaByte at its published widths as `serve_evabyte_sessions_decode` serves
# it: one stage of a four-stage pipeline (8 of 32 layers), 24 lanes over a
# windowed pool of 576 blocks of 128 rows of 4,096 columns, requests of
# 15,104 bytes at most, the prefill programs over 4 lanes x 512 and x 128
# and over one, the compaction over 4 (`aot_evabyte_sizes.programs`, made
# together).  Both pools donated and left where they are, the
# compaction's too (it slices a window's blocks out and writes a summary
# block back with a loop of its own, and reads eva_mu and eva_phi alone); no
# matrix converted or transposed in any program (mu, phi and the norms are
# the megabyte), none copied in the T=1 step and the compaction; a prefill
# program re-lays wq, wk and wv of each layer for a [2048, 4096] x [4096,
# 32, 128] product (PERF.md section 7) and its scratch is its 2,048 rows'
# activations (98 MB at [4, 512]; until PR 38 also every row's table
# gathered whole and scored dense, 0.96 GB of the cell's peak); the T=1 step
# on PR 32's paged kernel as it stands, over a table of 22 blocks (the
# sawtooth's peak), whose 24 x 1 blocks of a megabyte are not read and
# written back for a row each; no half of a pool copied out to be gathered
# from (`_table_blocks`).
def _evabyte_extra(text, memory, pools, row):
    assert "mini-gather" not in text
    if not row.which:
        assert not _whole_contexts(text, row.rows, 22, 128)
    if row.t == 1:
        assert "s32[24,22]" in text         # the table: lanes x peak blocks


_EVABYTE = CellProgram(
    "serve_evabyte_sessions_decode", "t512_4_rows", _from_evabyte_tool, 512,
    4, pools=(((8, 576, 128, 4096), BF16),) * 2, arguments=(12.9e9, 13.0e9),
    kernels={"paged_rows_write": 1}, temps=160 * MIB, row_pools=0,
    weights=(3.2e9, 3.3e9), all_bf16=True, forbidden=("transpose", "remat"),
    copied_most=((("convert",), MIB), (("copy",), 8 * 3 * 4096 * 4096 * 2)),
    extra=_evabyte_extra)


# dots3, Trinity, Falcon-H1 and Nemotron-3: the cell's T=1 step and an
# admission's one-row program at the cell's own sizes, from its tool.
#
# dots3: all three pools (the full layers' latent rows and index keys, the
# window layers' rows) donated and left where they are; a full layer's
# latent and index rows go in ONE write call, a window layer's in another
# (three bodies of full layers, two of window layers); the indexed layers
# choose their 2,048 rows of 17,024 without sorting a lane's scores
# (`select_sorts` 0: the kernel `sparse_select`, a trip of a chunk's rows
# `sparse_select_chunk`).
def _dots3_extra(text, memory, pools, row):
    assert count_select_sorts(text, 17024) == 0         # the table's rows


_DOTS3 = CellProgram(
    "serve_dots3_docs_decode", "t1_64_lanes", _from_tool("aot_dots3_sizes"),
    1, 64, which="t1",
    pools=(((3, 1536, 128, 640), BF16), ((3, 1536, 128, 128), BF16),
           ((6, 768, 128, 1152), BF16)), arguments=(11.3e9, 11.6e9),
    kernels={"paged_rows_write": 5, "sparse_index_scores": None,
             "sparse_select": 3, "sparse_latent_decode_attention": None,
             "window_latent_decode_attention": None,
             "moe_grouped_matmul": None},
    extra=_dots3_extra)

# Trinity: all four pools (the full layers' K and V rows, the window
# layers'), a K and a V pool written in ONE call a layer body; five runs of
# like layers, a layer body each: S S | S | F | S S S | F.
_TRINITY = CellProgram(
    "serve_trinity_docs_decode", "t1_64_lanes",
    _from_tool("aot_afmoe_sizes"), 1, 64, which="t1",
    pools=(((2, 1536, 128, 512), BF16),) * 2
    + (((6, 768, 128, 512), BF16),) * 2, arguments=(12.7e9, 12.8e9),
    kernels={"paged_rows_write": 5, "moe_grouped_matmul": 4 * 3,
             "paged_decode_attention": 2,
             "window_paged_decode_attention": 3})

# Falcon-H1: the K and V pools AND the 2.45 GB state buffer donated and left
# where they are beside the snapshot pool (no copy of it, no whole layer of
# it sliced out or stacked back: one copy is 2.4 GB); K and V in one write
# call a body.
_FALCONH1 = CellProgram(
    "serve_falconh1_chat_decode", "t1_64_lanes",
    _from_tool("aot_falconh1_sizes"), 1, 64, which="t1",
    pools=(((9, 768, 128, 512), BF16),) * 2
    + (((9, 65, 32, 256, 128), F32), ((9, 65, 15360), BF16)),
    arguments=(12.6e9, 12.8e9), padded=True, row_pools=2,
    snapshots=16 * (4 * 32 * 256 * 128 + 2 * 3 * 5120) * 9,
    kernels={"paged_rows_write": 1, "paged_decode_attention": None,
             "ssm_update": None})


# Nemotron-3 at published widths: K and V pools over the 2 attention layers,
# the state buffers over the 6 mixer layers with two heads of 64 folded into
# a lane row (0.82 GB, not the 1.64 a minor of 64 would pad to); the
# experts' matrices read where they lie (as [K, 1856] the up matrix was laid
# out K-minor and copied whole every step: 3.2 GB; it is held [1856, K]).
def _nemotronh_extra(text, memory, pools, row):
    copied = count_weight_bytes_copied(
        text, jax.eval_shape(lambda: {"w": jnp.zeros((5, 64, 1856, 2688),
                                                     jnp.bfloat16)}))
    assert not copied.get("copy") and not copied.get("transpose")


_NEMOTRONH = CellProgram(
    "serve_nemotron3_agents_decode", "t1_64_lanes",
    _from_tool("aot_nemotronh_sizes"), 1, 64, which="t1",
    pools=(((2, 2048, 128, 256), BF16),) * 2
    + (((6, 65, 32, 128, 128), F32), ((6, 65, 18432), BF16)),
    arguments=(8.6e9, 8.8e9), padded=True, row_pools=2, temps=0.2e9,
    kernels={"paged_rows_write": 2,             # the 2 attention layers
             "moe_grouped_matmul": 2 * 5,       # up and down, 5 E
             "paged_decode_attention": None, "ssm_update": 6},
    extra=_nemotronh_extra)

# LFM2 (PR 54) and Kimi-Linear (PR 57): the T=1 step at 128 lanes and the
# widest pair's program (128 + 4 x 256 rows), through the engine's entry.
#
# LFM2: K and V pools over the 2 attention layers and the state part's ONE
# buffer, the tails of the 7 conv layers (no float32 state anywhere), all
# three donated and left where they are; no weight copied or transposed
# beyond what the other expert cells' programs do (a layer's slice
# prefetched by XLA's own `copy-done`); the grouped multiply three times an
# expert run (four bodies), the paged kernel once an attention run (two).
# The conv mixers' per-lane part is XLA's own fusions: its one-token form
# works on the slots' rows as they are stored, so that the tails' buffer is
# neither gathered from nor laid out anew (the chunk's form alone,
# `_gated_conv` over [B, K - 1, D], turned the whole 7.4 MB buffer twice a
# T=1 program and 22 times a pair's).
_LFM2 = CellProgram(
    "serve_lfm2_rag_decode", "t1_128_lanes", _from_entry, 1, 0, lanes=128,
    pools=(((2, 5376, 128, 512), BF16),) * 2 + (((7, 129, 2 * 2048), BF16),),
    arguments=(13.1e9, 13.3e9), padded=True, row_pools=2, temps=0.1e9,
    weights=(10.35e9, 10.37e9), forbidden=("copy", "transpose", "remat"),
    copied_most=((("convert",), MIB),
                 (("slice", "dynamic-slice"), 5 * MIB)),
    kernels={"moe_grouped_matmul": 12, "paged_decode_attention": 2,
             "paged_rows_write": 2})

# Kimi-Linear: ONE latent pool over the 2 latent layers, the float32 states
# and the bf16 tails over the 6 KDA layers (a copy of the states is 1.6 GB),
# beside the 0.42 GB of snapshots; `kda_update` once a KDA run (three
# bodies) in both programs, `kda_scan` beside it in the pair's, the latent
# kernel once a latent run (two), the grouped multiply three times an
# expert run (four bodies).  No weight is transposed or made again; what the
# counter reads as `copy` and `convert` are activations of the 128 lanes
# that have a weight's shape ([128, 2304] the stream and `w_fa` turned
# round, [128, 4096] the decay's rows and `w_fb`: the gates' rank is the
# lane count), 22 MB where the weights are 7.5 GB.
_KIMILINEAR = CellProgram(
    "serve_kimilinear_reasoning_decode", "t1_128_lanes", _from_entry, 1, 0,
    lanes=128,
    pools=(((2, 3840, 128, 640), BF16), ((6, 129, 32, 128, 128), F32),
           ((6, 129, 3 * 12288), BF16)),
    arguments=(10.4e9, 10.6e9), padded=True, row_pools=1, temps=0.25e9,
    snapshots=32 * 6 * (4 * 32 * 128 * 128 + 2 * 3 * 12288),
    weights=(7.53e9, 7.56e9), forbidden=("transpose", "remat"),
    copied_most=((("copy", "convert"), 24 * MIB),),
    kernels={"moe_grouped_matmul": 12, "paged_rows_write": 2,
             "latent_decode_attention": 2, "kda_update": 3})


def _less(kernels, *names):
    return {k: v for k, v in kernels.items() if k not in names}


_also = dataclasses.replace
CELL_PROGRAMS = [
    _AXK1,
    _also(_AXK1, name="t512_4_prefill_lanes", t=512, rows=4, **_AXK1_CHUNK),
    _also(_AXK1, name="t128_4_prefill_lanes", t=128, rows=4, **_AXK1_CHUNK),
    _also(_EVABYTE, name="t1_24_lanes", t=1, rows=24, temps=64 * MIB,
          row_pools=None, kernels={"paged_rows_write": 1,
                                 "paged_decode_attention": None},
          **_NO_WEIGHT_MOVED),
    _EVABYTE,
    _also(_EVABYTE, name="t128_4_rows", t=128),
    _also(_EVABYTE, name="t512_one_row", rows=1),
    _also(_EVABYTE, name="t128_one_row", t=128, rows=1),
    _also(_EVABYTE, name="compaction_4_rows", t=0,
          which="compaction rows=4", kernels={}, temps=256 * MIB,
          arguments=(0, 2 * 2 * 8 * 576 * 128 * 4096 + MIB),
          **_NO_WEIGHT_MOVED),
    _DOTS3,
    _also(_DOTS3, name="t128_one_row", t=128, rows=1, which="short",
          kernels={"paged_rows_write": 5, "sparse_index_chunk_scores": None,
                   "sparse_select_chunk": 3,
                   "sparse_latent_chunk_attention": None,
                   "window_latent_chunk_attention": None,
                   "moe_grouped_matmul": None}),
    _TRINITY,
    _also(_TRINITY, name="t128_one_row", t=128, rows=1, which="short",
          kernels=_less(_TRINITY.kernels, "paged_decode_attention",
                        "window_paged_decode_attention")),
    _FALCONH1,
    _also(_FALCONH1, name="t64_one_row", t=64, rows=1, which="short",
          kernels={"paged_rows_write": 1, "ssm_scan": None}),
    _NEMOTRONH,
    _also(_NEMOTRONH, name="t64_one_row", t=64, rows=1, which="short",
          kernels={**_less(_NEMOTRONH.kernels, "paged_decode_attention",
                           "ssm_update"), "ssm_scan": 6}),
    _LFM2,
    _also(_LFM2, name="pair_128_4x256", t=256, rows=4,
          kernels={**_LFM2.kernels, "paged_rows_write": 4}),
    _KIMILINEAR,
    _also(_KIMILINEAR, name="pair_128_4x256", t=256, rows=4,
          kernels={**_KIMILINEAR.kernels, "paged_rows_write": 4,
                   "kda_scan": 3}),
]


@pytest.mark.parametrize("row", CELL_PROGRAMS,
                         ids=lambda row: f"{row.cell}:{row.name}")
def test_a_cells_programs_fit_a_v5e_and_leave_their_buffers_in_place(
        v5e, as_on_chip, row):
    """A serve cell's T=1 step and its admission's programs, compiled for
    the chip at the cell's own sizes: the pools and state buffers are the
    shapes the row names, all donated and left where they are (the alias
    is their bytes; no copy of one, no block of a rows' pool read and
    written back for a row), arguments and temporaries fit under the
    compiler's 15.75 GB beside the snapshots, the arguments are the bytes
    the cell's weights and pools make, the weights are read where they lie,
    and the kernels are there under the names the benchmark's readers find
    them by, as often as the layers' runs have bodies."""
    text, memory, pools, params = row.source(row, v5e[0])
    assert [(tuple(p.shape), p.dtype) for p in pools] == list(row.pools)
    held = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert held <= memory.alias_size_in_bytes <= held * (
        1.002 if row.padded else 1)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + row.snapshots < 15.75 * 2 ** 30)
    assert row.arguments[0] < memory.argument_size_in_bytes \
        < row.arguments[1]
    if row.temps:
        assert memory.temp_size_in_bytes < row.temps
    for p in pools:
        assert count_pool_copies(text, tuple(p.shape)) == 0, p.shape
    for p in pools[:row.row_pools]:
        assert not _pool_block_updates(text, p.shape)
    if row.weights:
        served = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(params))
        assert row.weights[0] < served < row.weights[1]
    if row.all_bf16:
        assert {x.dtype for x in jax.tree.leaves(params)} == {BF16}
    if row.forbidden or row.copied_most:
        copied = count_weight_bytes_copied(text, params)
        assert not set(copied) & set(row.forbidden), copied
        for kinds, most in row.copied_most:
            assert sum(copied.get(k, 0) for k in kinds) <= most, copied
    counts = _kernel_counts(text)
    assert set(counts) == set(row.kernels)
    # (a served iteration's tokens fill no tile: its experts' rows go back
    # by `ys[rank]`, `decoder.COMBINE_FROM`)
    assert "moe_combine" not in counts
    for name, often in row.kernels.items():
        assert often is None or counts[name] == often, (name, counts)
    if row.extra:
        row.extra(text, memory, pools, row)


# ---------------------------------------------------------------------------
# `train_mellum2_8k_ep4share`'s step, from the cell's own files
# ---------------------------------------------------------------------------

def test_the_moe_train_cells_step_fits_a_v5e_and_adds_no_expert_stack(
        as_on_chip):
    """Mellum 2's train step at the cell's shapes (2 x 8,192 tokens, 16 of
    64 experts held, one period S S S F, AdamW over float32 state) compiled
    for a described v5e.  The kernels it calls: the flash kernels under
    both names (forward and the one backward kernel, a call each in the
    window run's loop body and in the full run's: remat keeps their result
    and logsumexp, `decoder.REMAT_KEEPS`, and makes no forward again), the
    grouped multiply forward and dx and its dw (a run's loop body holds
    them for the first page of sorted rows, whose products remat keeps
    too, and once more in the loops over the pages behind it, which run
    only when routing passes the bound; a run's fifteen: the first page's
    three and their three dx, the later pages' three, made again in their
    backward's loop beside their three dx), the combine of the experts'
    rows (a run's four: the first page's y and dx, the later pages' in
    their two loops), and the loss head's two.  The experts are scanned with
    their layer and the runs' layers are their stack's `lax.split`: no two
    cotangents of a stack's size are summed anywhere (a layer's gradient
    goes into its place by a dynamic-update-slice, the two runs' into
    theirs by one concatenation), nothing copies a layer's or a stack's
    experts,
    and the step fits the chip: the compiler refuses one that does not
    (the same step without remat: 17.87 of 15.75 GiB), so compiling is
    the check, and the temporaries it reports are held under what they
    were (arguments: the 9.52 GB of ISSUE 61 less the gradients, which
    are temporaries)."""
    from benchmark.tools import aot_train_sizes
    try:
        cfg, compiled = aot_train_sizes.compile_step(
            "mellum2-12b-a2.5b", "train_b2x8192_moe")
    except RuntimeError as e:           # no v5e topology can be described
        pytest.skip(str(e))
    assert cfg.remat and cfg.n_experts_held == 16
    text, memory = compiled.as_text(), compiled.memory_analysis()
    counts = _kernel_counts(text)
    assert counts == {"window_flash_attention": 2, "flash_attention": 2,
                      "moe_grouped_matmul": 30, "moe_grouped_matmul_dw": 12,
                      "moe_combine": 8, "logits_lse": 1,
                      "loss_head_grads": 1}, counts
    # the flash kernels read K and V, and write dk and dv, at the model's 4
    # kv heads: beside q, dO and O at 32 heads no call takes or gives
    # another array that wide
    calls = [line for line in text.splitlines() if " custom-call(" in line
             and "flash_attention" in line.split(" = ")[0]]
    assert len(calls) == 4
    for line in calls:
        forward = "f32[64,1,8192]" in line.split(" custom-call(")[0]
        assert line.count("bf16[2,8192,4096]{") == (2 if forward else 4)
        assert line.count("bf16[2,8192,512]{") == (2 if forward else 4)
    # the experts' rows go back to their tokens through `moe_combine`: no
    # operation makes a row for every one of the 131,072 assignments
    assert not re.findall(r" = \w+\[131072,2304\]", text)
    # (`add_any` is a sum of cotangents; the optimizer's own sums over its
    # moments, once a step, are not)
    stack = r"f32\[\d,16,(2304,896|896,2304)\]"
    summed = [line for line in text.splitlines()
              if re.search(rf" = {stack}\S* add\(", line)
              and "add_any" in line]
    assert not summed, summed[:2]
    # a layer's gradient is written into the stack where it is
    assert any(re.search(stack, line) and "dynamic-update-slice" in line
               for line in text.splitlines())
    experts = r"\w+\[(\d,)?16,(2304,896|896,2304)\]"
    assert not re.findall(rf" = {experts}\S* (copy|transpose)\(", text)
    assert 7.1e9 < memory.argument_size_in_bytes < 7.2e9
    assert memory.alias_size_in_bytes > 7.1e9       # the state, donated
    assert memory.temp_size_in_bytes < 11.4e9
