"""The flash kernels' forward and gradients against `jax.grad` of the plain
reference, on the CPU interpreter: tiles under, on and (non-causal) over the
diagonal, a head of one block and of many, tiles smaller than a block, and
how the heads lie in a block of 128 columns: two of 64 side by side, one
alone in the last block of an odd count, one of 128 or 256 a block, and a kv
head's group of query heads, whose products are one product each."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A
from ray_tpu.ops import flash_attention, reference_attention

# (q_len, kv_len, blocks or None for the defaults)
SHAPES = [
    (256, 256, (128, 128)),
    (512, 512, (128, 64)),
    (384, 384, None),
    (1536, 1536, None),
    (1024, 1024, None),
]


def _grads(fn, g):
    return jax.grad(lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)),
        argnums=(0, 1, 2))


def _check(q_len, kv_len, blocks, d, dtype, causal, scale=None, heads=None,
           apart=False, window=0, kv_heads=None):
    """out, dq, dk and dv of `heads` heads over `kv_heads` of K and V (the
    heads': no groups), each head (dk, dv: each kv head) against the
    reference's: float32 to 2e-5, bf16 to two ulps at the head's largest
    value.  With `apart`, the cotangent of a head is a hundredth of its
    neighbour's and v of a kv head a hundred times its neighbour's (1,
    100, 0.01, 1, ...): a head that reached its neighbour's lanes, or its
    neighbour's columns of a product they share, would drown it, and there
    alone the float32 limit too goes by the head's largest value (values
    of 100 do not round to 2e-5)."""
    if heads is None:
        heads = 2 if q_len <= 512 else 1
    kv_heads = kv_heads or heads
    q, k, v, g = (jax.random.normal(
        jax.random.fold_in(jax.random.key(q_len + d), i),
        (1, kv_len, kv_heads, d) if i in (1, 2) else (1, q_len, heads, d),
        jnp.float32) for i in range(4))
    if apart:
        size = lambda n: (100.0 ** ((jnp.arange(n) + 1) % 3 - 1))[:, None]
        v, g = v * size(kv_heads), g / size(heads)
    q, k, v, g = (x.astype(dtype) for x in (q, k, v, g))
    blocks = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, **blocks)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)

    got = (flash(q, k, v),) + _grads(flash, g)(q, k, v)
    want = (ref(q, k, v),) + _grads(ref, g)(q, k, v)
    for name, xs, ys in zip(("out", "dq", "dk", "dv"), got, want):
        xs, ys = (np.asarray(a, np.float32) for a in (xs, ys))
        assert xs.shape == ys.shape and np.isfinite(xs).all(), name
        for head in range(xs.shape[2]):
            x, y = xs[:, :, head], ys[:, :, head]
            if dtype == jnp.float32:
                atol = 2e-5 * (max(1.0, float(np.abs(y).max())) if apart
                               else 1.0)
                np.testing.assert_allclose(x, y, atol=atol, rtol=2e-5,
                                           err_msg=f"{name} of head {head}")
            else:   # chip_smoke.py's rule: two bf16 ulps at the largest value
                tol = 2.0 ** -6 * max(1.0, float(np.abs(y).max()))
                assert float(np.abs(x - y).max()) <= tol, (name, head)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("q_len,kv_len,blocks", SHAPES,
                         ids=[f"{s[0]}-{s[2]}" for s in SHAPES])
def test_flash_forward_and_gradients_match_reference(q_len, kv_len, blocks,
                                                     causal, dtype):
    _check(q_len, kv_len, blocks, 64, dtype, causal)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_gradients_at_a_head_of_128(causal, dtype):
    _check(256, 256, (128, 128), 128, dtype, causal)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_gradients_at_two_blocks_a_head_of_128(dtype):
    """2,048 positions are two blocks of 1,024: the pair on the diagonal,
    its crossed tiles of 512 as sub-tiles of 256 in the backward, beside a
    pair under it walked whole, each under its `pl.when`."""
    _check(2048, 2048, None, 128, dtype, True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_gradients_of_two_heads_at_blocks_of_512(dtype):
    """Two heads of 64 in a block, two blocks of 512 a head: the heads'
    scores one product in every tile, on the diagonal (one tile of 512 in
    the forward, sub-tiles of 256 and 128 in the backward) and under it."""
    _check(1024, 1024, (512, 512), 64, dtype, True, heads=2, apart=True)


# (length, block, window, head width): windows below, at and above the
# block, over 2 to 8 blocks, heads of 64 (two a column block) and of 128
WINDOWS = [
    (256, 128, 50, 64), (256, 128, 128, 128), (512, 128, 200, 64),
    (512, 128, 1, 64), (640, 128, 129, 128), (1024, 128, 128, 64),
    (768, 256, 600, 128), (1024, 256, 384, 64), (1024, 512, 600, 128),
    (512, 256, 255, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("length,block,window,d", WINDOWS,
                         ids=[f"{w[0]}-{w[1]}-w{w[2]}-d{w[3]}"
                              for w in WINDOWS])
def test_flash_with_a_window_matches_the_masked_reference(length, block,
                                                          window, d, dtype):
    """out, dq, dk and dv of `flash_attention(window=)` against the plain
    attention under the causal mask and the window's: a q block visits its
    own kv block and those the window reaches and no others, the tiles the
    window's edge crosses masked by a second diagonal (in the backward cut
    into sub-tiles as the first diagonal's are)."""
    _check(length, length, (block, block), d, dtype, True, window=window,
           heads=2 if d == 64 else 1, apart=d == 64)


def test_a_window_as_long_as_the_sequence_is_no_window(monkeypatch):
    """The calls with a window carry a name of their own, and a window
    that every position fits in is the causal attention, under its name."""
    names = []
    call = A.pl.pallas_call
    monkeypatch.setattr(A.pl, "pallas_call", lambda *a, **kw: (
        names.append(kw["name"]), call(*a, **kw))[1])
    q = jnp.ones((1, 256, 2, 64), jnp.float32)
    for window, name in ((256, "flash_attention"),
                         (255, "window_flash_attention")):
        del names[:]
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, q, window=window, block_q=128, block_k=128)))(q)
        assert len(names) >= 2 and set(names) == {name}
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_gradients_of_a_ring_step(dtype):
    """Non-causal, an explicit scale, more keys than queries."""
    _check(256, 512, (128, 128), 64, dtype, False, scale=0.1)


# (heads, d, whether the heads' sizes lie apart): how heads fill a block of
# max(d, 128) columns of the [b, l, heads x d] arrays the kernels read.
HEADS = [
    (2, 64, True),      # two a block: a head's lanes must not reach the other
    (3, 64, True),      # the second block holds one head and half a block
    (25, 64, False),    # gpt2-xl: twelve whole blocks and a thirteenth half
    (1, 128, False),    # a head a block
    (2, 128, True),
    (1, 256, False),    # a head a block of 256 columns
]


def test_the_interpreter_poisons_what_a_block_reads_past_the_array():
    """The last of an odd count's blocks is half a head's lanes and half
    whatever the copy left: under the interpreter NaN, so a kernel that
    let those lanes reach a result would fail the odd cases below."""
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    got = pl.pallas_call(
        copy, grid=(2,), interpret=True,
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
    )(jnp.ones((8, 192), jnp.float32))
    assert np.isnan(np.asarray(got[:, 192:])).all()
    assert (np.asarray(got[:, :192]) == 1).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("heads,d,apart", HEADS,
                         ids=[f"{h[0]}x{h[1]}" for h in HEADS])
def test_flash_heads_side_by_side_in_a_block(heads, d, apart, causal, dtype):
    _check(256, 256, (128, 128), d, dtype, causal, heads=heads, apart=apart)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [2, 3])
def test_flash_gradients_of_a_ring_step_at_heads_apart(heads, dtype):
    _check(256, 512, (128, 128), 64, dtype, False, scale=0.1, heads=heads,
           apart=True)


# (heads, kv heads, d, length, block, "causal" / "full" / a window): a kv
# head's group of query heads takes a grid step, their scores ONE product
# [kv, heads x q] of the shared K tile, as are V^T P, dP, dq^T, and dv and dk,
# which contract over the group's q positions.  A group of 64-wide heads
# (narrower than a block's lanes; llama-1b's 32 over 4) runs the kernels of
# a group of one over K and V repeated, dk and dv summed after them.
GROUPS = [
    (4, 2, 128, 256, 128, "causal"),        # two kv heads, groups of 2
    (4, 1, 128, 256, 128, "full"),
    (8, 1, 128, 256, 128, "causal"),
    (8, 2, 128, 512, 128, 200),             # the edge crosses a tile
    (8, 1, 128, 512, 256, 255),
    # tiles of 512 hold two heads a product: a group of 4 is two products,
    # on the diagonal the backward's sub-tiles of 128
    (4, 1, 128, 1024, 512, "causal"),
    (2, 1, 256, 256, 128, "causal"),
    (4, 2, 64, 256, 128, "causal"), (8, 1, 64, 256, 128, "full"),
    (4, 1, 64, 512, 128, 200),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,kv_heads,d,length,block,kind", GROUPS,
                         ids=["-".join(map(str, g)) for g in GROUPS])
def test_flash_of_a_kv_heads_group_of_query_heads(heads, kv_heads, d, length,
                                                  block, kind, dtype):
    like = [jax.ShapeDtypeStruct((1, length, n, d), dtype)
            for n in (heads, kv_heads)]
    plan = A._flash_plan(*like, kind != "full", None, block, block, True)
    if d < 128:
        assert (plan.spread, plan.group, plan.column_blocks) == (
            heads // kv_heads, 1, heads * d // 128)
        assert plan._replace(spread=1) == A._flash_plan(
            like[0], like[0], kind != "full", None, block, block, True)
    else:
        assert (plan.group, plan.fwd_part, plan.bwd_part, plan.spread,
                plan.column_blocks) == (heads // kv_heads,) * 3 + (1, kv_heads)
    assert A._flash_rows(plan, 1, length) == (heads, 1, length)
    _check(length, length, (block, block), d, dtype, kind != "full",
           heads=heads, kv_heads=kv_heads, apart=True,
           window=kind if isinstance(kind, int) else 0)


# (what shrinks, forward part, backward part) of a group of 4: VMEM that
# holds two heads' dq alone splits the backward, and straight-line code
# held to two blocks' pairs forward and one backward splits both, unlike
# (the train cell's plan: the forward 4 of 8, the backward 2): the forward
# then walks `j % n_blocks` with the part on the grid's second axis, and
# the logsumexp rows it writes two heads a step are read one a step.
PARTS = [("vmem", 4, 2), ("pairs", 2, 1)]


@pytest.mark.parametrize("window", [0, 200], ids=["causal", "window"])
@pytest.mark.parametrize("what,fwd_part,bwd_part", PARTS,
                         ids=[p[0] for p in PARTS])
def test_a_group_too_large_for_a_grid_step_is_walked_in_parts(
        monkeypatch, what, fwd_part, bwd_part, window):
    """A group of 4 whose heads do not all fit a grid step goes a part of
    them a step, the forward's and the backward's parts each their own: dk
    and dv gather over the backward's parts in scratch."""
    like = [jax.ShapeDtypeStruct((1, 512, n, 128), jnp.float32)
            for n in (8, 2)]
    whole = A._flash_plan(*like, True, None, 128, 128, True)
    assert (whole.group, whole.fwd_part, whole.bwd_part) == (4, 4, 4)
    if what == "vmem":
        monkeypatch.setattr(A, "_FLASH_VMEM_LIMIT", A._flash_scoped(
            A._flash_bwd_vmem(whole, 2, 512, 512, jnp.float32)))
    else:
        monkeypatch.setattr(A, "_FLASH_FWD_PAIRS", 2 * 128 * 128)
        monkeypatch.setattr(A, "_FLASH_BWD_PAIRS", 128 * 128)
    plan = A._flash_plan(*like, True, None, 128, 128, True)
    assert (plan.group, plan.fwd_part, plan.bwd_part) == (4, fwd_part,
                                                          bwd_part)
    A.flash_attention.clear_cache()
    try:
        _check(512, 512, (128, 128), 128, jnp.float32, True, heads=8,
               kv_heads=2, apart=True, window=window)
    finally:
        A.flash_attention.clear_cache()


# (heads, kv heads, d, heads a grid step forward and backward, the forward's
# tile and heads a product, MiB of VMEM forward and backward) at 8,192
# positions: the MoE train cell's calls, llama3-8b's, Gemma 2 9B's
CELLS = [(32, 4, 128, 4, 2, (256, 256, 4), 9.5, 16 + 8),
         (32, 8, 128, 4, 2, (256, 256, 4), 9.5, 16 + 8),
         (16, 8, 256, 2, 1, (512, 512, 2), 8 + 2, 16 + 16)]


@pytest.mark.parametrize("heads,kv_heads,d,fwd_part,bwd_part,tiles,fwd,bwd",
                         CELLS, ids=["-".join(map(str, c[:3])) for c in CELLS])
def test_a_groups_grid_step_holds_what_its_code_and_vmem_allow(
        monkeypatch, heads, kv_heads, d, fwd_part, bwd_part, tiles, fwd, bwd):
    """A grid step's straight-line code walks a block of 1,024 x 1,024
    pairs for each of its heads of 128 columns, four forward and two
    backward (`_FLASH_FWD_PAIRS`: a kernel past 65,536 bundles runs at half
    its speed), half as many heads of 256, whatever VMEM would hold;
    shorter blocks take the whole group.  And the scoped VMEM a call asks
    for is what the plan held against `_FLASH_VMEM_LIMIT`, all of it: the
    backward's dq^T AND the dk and dv it gathers over the parts."""
    length, mib = 8192, 2 ** 20

    def like(length):
        return [jax.ShapeDtypeStruct((2, length, n, d), jnp.bfloat16)
                for n in (heads, kv_heads)]

    cell = A._flash_plan(*like(length), True, None, 1024, 1024, True)
    assert (cell.group, cell.fwd_part, cell.bwd_part, cell.column_blocks,
            cell.lanes, cell.heads) == (heads // kv_heads, fwd_part, bwd_part,
                                        kv_heads, d, 1)
    assert A._flash_rows(cell, 2, length) == (2 * heads, 1, length)
    assert A._flash_fwd_tiles(cell, fwd_part) == tiles
    assert A._flash_fwd_tiles(cell, 1) == (512, 512, 1)
    assert A._flash_fwd_vmem(cell, fwd_part, jnp.bfloat16) == fwd * mib
    assert A._flash_bwd_vmem(cell, bwd_part, length, length,
                             jnp.bfloat16) == bwd * mib
    short = A._flash_plan(*like(2048), True, None, 256, 256, True)
    assert (short.fwd_part, short.bwd_part) == (cell.group,) * 2
    # what the calls ask for, forward and backward
    asked = []
    call = A.pl.pallas_call
    monkeypatch.setattr(A.pl, "pallas_call", lambda *a, **kw: (
        asked.append(kw["compiler_params"].vmem_limit_bytes),
        call(*a, **kw))[1])
    q, k = (jax.ShapeDtypeStruct((2, length, n * d), jnp.bfloat16)
            for n in (heads, kv_heads))
    lse = jax.ShapeDtypeStruct(A._flash_rows(cell, 2, length), jnp.float32)
    jax.eval_shape(lambda *x: A._flash_fwd_heads(cell, *x), q, k, k)
    jax.eval_shape(lambda *x: A._flash_bwd_heads(cell, *x), q, k, k, q, q,
                   lse)
    assert asked == [(fwd + 16) * mib, 2 * bwd * mib]
    assert max(asked) <= A._FLASH_VMEM_LIMIT
    # a short sequence's few bytes ask for no less than the default beside
    # them (12 MiB for this backward was refused on the chip), fewer none
    assert A._flash_scoped(6 * mib) == 22 * mib
    del asked[:]
    q, k = (jax.ShapeDtypeStruct((1, 2048, n * d), jnp.bfloat16)
            for n in (heads, kv_heads))
    lse = jax.ShapeDtypeStruct(A._flash_rows(cell, 1, 2048), jnp.float32)
    jax.eval_shape(lambda *x: A._flash_bwd_heads(cell, *x), q, k, k, q, q,
                   lse)
    held = A._flash_bwd_vmem(cell, bwd_part, 2048, 2048, jnp.bfloat16)
    assert asked == [held + 16 * mib] and held == bwd * mib / 4


def test_heads_that_are_no_whole_groups_are_refused():
    q, k = jnp.ones((1, 256, 3, 128)), jnp.ones((1, 256, 2, 128))
    with pytest.raises(ValueError, match="whole groups"):
        flash_attention(q, k, k)


def test_a_mesh_splits_whole_groups_to_a_shard():
    """Two shards of the heads take a kv head and its two query heads each;
    four would cut the groups."""
    q, k, v = (jax.random.normal(jax.random.key(i), (2, 256, n, 128))
               for i, n in enumerate((4, 2, 2)))
    mesh = jax.make_mesh((2,), ("tensor",), devices=jax.devices()[:2])
    got = jax.jit(lambda q, k, v: A.mesh_flash_attention(
        q, k, v, mesh=mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(got, reference_attention(q, k, v),
                               atol=2e-5, rtol=2e-5)
    mesh = jax.make_mesh((4,), ("tensor",), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="cut the groups"):
        A.mesh_flash_attention(q, k, v, mesh=mesh)


def test_the_model_hands_k_and_v_over_at_their_own_heads():
    """`heads_attention` at `mellum-nano`'s sizes (4 heads over 2 kv heads):
    what reaches `flash_attention` is K and V at 2 heads, and nothing
    beside the call spreads an array over a group (a repeat is a
    `broadcast_in_dim` to [.., kv heads, group, d])."""
    from ray_tpu.models import decoder, mellum

    c = mellum.CONFIGS["mellum-nano"]
    sizes = c.sizes(mellum.WINDOW)
    params = mellum.init_params(c, jax.random.key(0))["blocks"]
    h = jnp.ones((2, 32, c.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda h, p: decoder.heads_attention(
        h, p, mellum.spec(c), sizes, None))(
            h, {name: w[0] for name, w in params.items()}).jaxpr
    (call,) = [e for e in jaxpr.eqns
               if e.params.get("name") == "flash_attention"]
    assert [v.aval.shape for v in call.invars] == [
        (2, 32, n, c.head_dim) for n in (c.n_heads, c.n_kv_heads,
                                         c.n_kv_heads)]
    group = c.n_heads // c.n_kv_heads
    spread = [e for e in jaxpr.eqns if e.primitive.name == "broadcast_in_dim"
              and group in e.outvars[0].aval.shape[2:]
              and e.outvars[0].aval.ndim > 4]
    assert group > 1 and not spread, spread


# sha256 of `_jaxpr_text` below at the tree before PR 63 (`git archive
# 456b160`, this same function): a call with no groups is the kernels that
# tree ran, forward and backward, equation for equation.  A PR that changes
# the kernels on purpose records its own.
NO_GROUPS = [
    ((12, 64, 1024), "24029c4ba04513ad"), ((25, 64, 1024), "34958b12cb8ecf11"),
    ((16, 128, 2048), "e035f4ee2c64a8a6"), ((4, 256, 1024), "965240b167be5497"),
    ((2, 64, 2048, 700), "65a9d8d5b631a127"),
    ((2, 128, 2048, 1024), "9d7449cd1af4eb7e"),
    ((2, 64, 256, 0, jnp.float32, False), "bc0ce44608dd0bb2"),
]


def _jaxpr_text(h, d, length, window=0, dtype=jnp.bfloat16, causal=True):
    x = jax.ShapeDtypeStruct((2, length, h, d), dtype)

    def step(q, k, v):
        f = lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, causal=causal, window=window,
            interpret=False).astype(jnp.float32))
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    return str(jax.make_jaxpr(step)(x, x, x))


@pytest.mark.parametrize("case,digest", NO_GROUPS,
                         ids=["-".join(map(str, c[:4])) for c, _ in NO_GROUPS])
def test_without_groups_the_kernels_are_those_before_groups(case, digest):
    assert hashlib.sha256(
        _jaxpr_text(*case).encode()).hexdigest()[:16] == digest


def test_flash_plan_puts_whole_heads_in_blocks_of_128_columns():
    def like(h, d):
        return jax.ShapeDtypeStruct((2, 256, h, d), jnp.bfloat16)

    for h, d, lanes, heads, blocks in [(12, 64, 128, 2, 6), (25, 64, 128, 2, 13),
                                       (16, 128, 128, 1, 16),
                                       (4, 256, 256, 1, 4)]:
        plan = A._flash_plan(like(h, d), like(h, d), True, None, 1024, 1024,
                             True)
        assert (plan.lanes, plan.heads, plan.column_blocks) == (
            lanes, heads, blocks)
        # as before there were groups, and the whole of a group of one
        assert plan == A._FlashPlan(256, 256, True, 1.0 / np.sqrt(d), True, d,
                                    h * d)
        # a float32 row a head of every block, the odd count's phantom too
        assert A._flash_rows(plan, 2, 256) == (2 * blocks * heads, 1, 256)


def test_flash_tiles_follow_the_blocks():
    assert A._flash_tile(1024, 512) == 512 and A._flash_tile(1024, 256) == 256
    assert A._flash_tile(384, 512) == 128 and A._flash_tile(96, 256) == 96
    # the diagonal block of 1,024 in whole tiles, as the forward walks it at
    # 512: 3 of 4, the 2 the diagonal crosses masked (at 256: 10 of 16, 4)
    whole = list(A._tiles(2, 512, 2, 512, True))
    assert whole == [(0, 512, 0, 512, 0), (512, 512, 0, 512, None),
                     (512, 512, 512, 512, 0)]
    assert len(list(A._tiles(4, 256, 4, 256, True))) == 10
    # as the backward walks it at one head a block: the crossed tiles as
    # three sub-tiles of 256 each, the one under the diagonal unmasked and
    # the two it crosses masked from their own first pair
    halves = list(A._tiles(2, 512, 2, 512, True, 256))
    assert [t for t in halves if t[0] < 512] == [
        (0, 256, 0, 256, 0), (256, 256, 0, 256, None), (256, 256, 256, 256, 0)]
    assert len(halves) == 1 + 2 * 3 and sum(
        t[4] is not None for t in halves) == 4
    # and at two heads a block: those two cut again, nine tiles of 128 in a
    # crossed tile of 512 beside the tile of 256 under its diagonal
    tiles = list(A._tiles(2, 512, 2, 512, True, 128))
    assert [t[1:4:2] for t in tiles if t[0] < 512] == [
        (128, 128)] * 3 + [(256, 256)] + [(128, 128)] * 3
    assert len(tiles) == 1 + 2 * 7
    assert sum(t[4] is not None for t in tiles) == 8
    assert {t[4] for t in tiles} == {None, 0}
    # the tiles of a q tile of the whole size follow one another: the
    # backward makes delta and the heads' Q and dO once a q tile
    assert [t[0] // 512 for t in tiles] == sorted(t[0] // 512 for t in tiles)
    assert len(list(A._tiles(2, 512, 2, 512, False, 128))) == 4
    # 128 x 64: a q tile sees the kv tiles up to its last row
    assert [t[2:] for t in A._tiles(1, 128, 4, 64, True, 128)] == [
        (0, 64, 0), (64, 64, 64)]


# (block, tile at most, the smallest sub-tile of a crossed tile or 0 for
# whole tiles, the visited share of the block's square at most or None)
WALKS = [(1024, 512, 256, 0.63), (1024, 512, 128, 0.57),
         (1024, 256, 128, 0.57),
         (1024, 512, 0, 0.75), (1024, 256, 0, 0.625),
         (2048, 512, 128, None), (1536, 512, 128, None),
         (384, 512, 128, None), (96, 256, 128, None)]


def _pairs(tiles, masked):
    """How often each (q, k) of the tiles [(q0, rows, k0, columns, offset)]
    is computed and kept: all of an unmasked tile, of a masked one those
    `_masked` shows."""
    size = max(max(t[0] + t[1], t[2] + t[3]) for t in tiles)
    seen = np.zeros((size, size), int)
    for q0, nq, k0, nk, offset in tiles:
        if (offset is None) == masked:
            continue
        keep = np.ones((nk, nq), bool)
        if masked:      # S^T [kv, q], the mask as the kernels make it
            keep = np.asarray(A._masked(jnp.ones((nk, nq)), offset, nq)) == 1
        seen[q0:q0 + nq, k0:k0 + nk] += keep.T
    return seen


@pytest.mark.parametrize("block,most,least,area", WALKS,
                         ids=[f"{w[0]}-{w[1]}-{w[2]}" for w in WALKS])
def test_the_diagonal_walk_computes_every_visible_pair_once(block, most,
                                                            least, area):
    """Host-side, no kernel: the extents `_tiles` lists for a diagonal
    block cover every pair with k <= q exactly once and no unmasked extent
    holds a pair with k > q; with `least` a crossed tile of twice that or
    more is visited as sub-tiles (of a 1,024 square at most 0.63 at halves
    of 512 and 0.57 at tiles of 128, where whole tiles visit 0.75 and
    0.625), a crossed tile of 128 or less or an odd one as it was; off the
    diagonal every tile whole."""
    tile = A._flash_tile(block, most)
    n = block // tile
    tiles = list(A._tiles(n, tile, n, tile, True, least))
    unmasked, masked = _pairs(tiles, False), _pairs(tiles, True)
    q, k = np.indices((block, block))
    assert ((unmasked + masked) == (k <= q)).all()
    assert not unmasked[k > q].any()
    visited = sum(t[1] * t[3] for t in tiles) / block ** 2
    whole = list(A._tiles(n, tile, n, tile, True))
    assert whole == [
        (q0, tile, k0, tile, k0 - q0 if k0 + tile - 1 > q0 else None)
        for q0 in range(0, block, tile) for k0 in range(0, q0 + 1, tile)]
    if not least or tile % (2 * least):
        assert tiles == whole
    else:
        assert visited < sum(t[1] * t[3] for t in whole) / block ** 2
        assert min(min(t[1], t[3]) for t in tiles) == least
    if area is not None:
        assert 0.5 < visited <= area
    assert list(A._tiles(n, tile, n, tile, False, least)) == [
        (q0, tile, k0, tile, None) for q0 in range(0, block, tile)
        for k0 in range(0, block, tile)]


def test_the_diagonal_walk_of_tiles_that_are_not_square():
    """128 q positions by 64 kv positions a tile (no caller makes one: a
    causal block's tiles are square): not cut, two crossed tiles a q tile,
    every visible pair once."""
    tiles = list(A._tiles(2, 128, 4, 64, True, 128))
    q, k = np.indices((256, 256))
    assert ((_pairs(tiles, False) + _pairs(tiles, True)) == (k <= q)).all()
    assert not _pairs(tiles, False)[k > q].any()
    assert all(t[1::2] == (128, 64) for t in tiles)


# (block, tile at most, least sub-tile or 0, window)
WINDOW_WALKS = [(1024, 512, 0, 1024), (1024, 512, 256, 1024),
                (1024, 512, 128, 1024), (1024, 512, 256, 700),
                (1024, 512, 0, 300), (512, 256, 128, 1500),
                (256, 256, 0, 1), (1024, 512, 128, 2048)]


@pytest.mark.parametrize("block,most,least,window", WINDOW_WALKS,
                         ids=["-".join(map(str, w)) for w in WINDOW_WALKS])
def test_the_window_walk_computes_every_visible_pair_once(block, most, least,
                                                          window):
    """Host-side, no kernel: over the kv blocks a q block visits under a
    window (`_window_steps`), the extents `_tiles` lists cover every pair
    with 0 <= q - k < window exactly once, no unmasked extent holds any
    other, and the blocks behind the window are not visited at all."""
    tile = A._flash_tile(block, most)
    n = block // tile
    n_blocks = 6
    steps = A._window_steps(window, block, n_blocks)
    assert steps == min(-(-(window - 1) // block) + 1, n_blocks)
    q, k = np.indices((block, block))
    for r in range(n_blocks):
        tiles = list(A._tiles(n, tile, n, tile, True, least,
                              back=r * block, window=window))
        visible = (q + r * block - k >= 0) & (q + r * block - k < window)
        if r >= steps:
            assert not tiles and not visible.any()
            continue
        seen = np.zeros((block, block), int)
        for q0, nq, k0, nk, offset, edge in tiles:
            keep = np.asarray(A._masked(jnp.ones((nk, nq)), offset, nq,
                                        edge)) == 1
            if offset is None and edge is None:
                assert visible[q0:q0 + nq, k0:k0 + nk].all()
            seen[q0:q0 + nq, k0:k0 + nk] += keep.T
        assert (seen == visible).all()
        if least and tile % (2 * least) == 0 and not visible.all():
            assert min(min(t[1], t[3]) for t in tiles) == least
    # the block the window's edge crosses at 1,024 over blocks of 1,024 is
    # the diagonal block's mirror: one whole tile and two crossed ones
    if (block, most, least, window) == (1024, 512, 0, 1024):
        assert [t[4:] for t in A._tiles(2, 512, 2, 512, True, back=1024,
                                        window=1024)] == [
            (None, 0), (None, None), (None, 0)]


def test_the_mask_of_heads_side_by_side_is_each_heads_own():
    """Scores [kv, heads x q]: every head's columns carry the tile's mask."""
    one = np.asarray(A._masked(jnp.ones((128, 256)), -64, 256))
    both = np.asarray(A._masked(jnp.ones((128, 512)), -64, 256))
    assert (both[:, :256] == one).all() and (both[:, 256:] == one).all()
    assert (one == 1).sum() == sum(min(128, c + 65) for c in range(256))
    assert A._masked(both, None, 256) is both


def test_a_head_whose_dq_does_not_fit_vmem_takes_the_reference(monkeypatch):
    """The backward holds a head's dq in VMEM; past the limit the plan is
    None and the call runs (and differentiates) through the XLA reference."""
    like = jax.ShapeDtypeStruct((1, 1024, 1, 64), jnp.float32)
    assert A._flash_plan(like, like, True, None, 1024, 1024, True) is not None
    monkeypatch.setattr(A, "_FLASH_VMEM_LIMIT", 1024)
    assert A._flash_plan(like, like, True, None, 1024, 1024, True) is None
    A.flash_attention.clear_cache()
    try:
        _check(256, 256, (128, 128), 64, jnp.float32, True)
    finally:
        A.flash_attention.clear_cache()
