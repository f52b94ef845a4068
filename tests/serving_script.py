"""The cached forward by hand, once: what every family's "prefill in chunks,
then decode, against the reference" case runs.  A helper module, no tests
in it.

`step` is `family.forward_cached` and `family.lm_head` as ONE program (and
`forward` the uncached pass, `init_params` the seeded weights): the
family (a module) and its config are static, so jit keeps one trace a
(family, config, tree form, slice length) for as long as the process holds
its caches.  Called op by op, every primitive outside the layer scan is an
executable of its own, compiled and mapped (tests/conftest.py's guard): 55.6 s
against 9.9 s for one LFM2 case (ISSUE 59).  A trace reads the precision it
was made under, so a caller's `jax.default_matmul_precision` goes around
the call, as `serve`'s `precision` does.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _init(family, cfg, seed):
    return jax.jit(family.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))


def init_params(family, cfg, seed=0):
    """The family's seeded weights from ONE program a (family, config),
    made once a seed; the tree's containers are the caller's own to edit.
    (Called as it is, `init_params` draws leaf by leaf through closures
    made anew every call: 3 to 7 s and some 400 to 900 memory maps a call
    at nano size, every call.)"""
    return jax.tree.map(lambda leaf: leaf, _init(family, cfg, seed))


@functools.partial(jax.jit, static_argnums=(0, 3))
def forward(family, params, tokens, cfg):
    """`family.forward` as one program."""
    return family.forward(params, tokens, cfg)


@functools.partial(jax.jit, static_argnums=(0, 1))
def step(family, cfg, tree, tokens, positions, valid, k, v, tables, ctx_lens,
         load=None, slots=None):
    """(logits [B, T, V], k, v, load): one slice through the cached trunk
    and the head.  `load` None: the family's step carries no counters."""
    if load is None:
        x, k, v = family.forward_cached(tree, tokens, positions, valid, k, v,
                                        tables, ctx_lens, cfg, slots=slots)
    else:
        x, k, v, load = family.forward_cached(
            tree, tokens, positions, valid, k, v, tables, ctx_lens, cfg,
            load, slots=slots)
    return family.lm_head(tree, x, cfg), k, v, load


def serve(family, cfg, tree, cache, seqs, chunk, lanes, *, prefill,
          late=None, name_slots=False, pools=None, load=None,
          precision=None):
    """Feed `seqs` through `cache` (a `PagedKVCache.for_model(family, cfg)`
    whose allocator the caller may have dealt from, so that blocks come out
    of order): row i of every slice is lane `lanes[i]` and holds `seqs[i]`,
    or nothing where that is None (an idle row: no valid token, a context
    of 1).  The first `prefill[i]` tokens of a row go in slices of `chunk`
    (the last one padded), a row joining `late[i]` slices behind the first;
    the rest one token a slice, every row at its own depth.  The cache is
    kept as the engine keeps it: a lane's table grown before a slice,
    its length committed and what it no longer reads released behind it.

    `name_slots`: the rows name their lanes as their slots in the parts'
    buffers (else row i's is slot i, the form a decode step takes).
    `pools`: (k, v) in place of `cache.step_pools`.  `load`: the counters
    to start from, where the family's step carries them.

    Returns (a [len, V] array of logits a row, None for an idle one;
    (k, v) as the last slice left them; the load)."""
    k, v = cache.step_pools if pools is None else pools
    rows = range(len(seqs))
    lengths = [0 if seq is None else len(seq) for seq in seqs]
    late = late or [0] * len(seqs)
    for i in rows:
        if lengths[i]:
            cache.alloc_lane(lanes[i], lengths[i])
    slots = jnp.asarray(lanes, jnp.int32) if name_slots else None
    fed = [0] * len(seqs)
    got = [[] for _ in seqs]
    slices = 0
    while any(fed[i] < lengths[i] for i in rows):
        if any(fed[i] < min(prefill[i], lengths[i]) for i in rows):
            t = chunk
            counts = [min(chunk, prefill[i] - fed[i]) if slices >= late[i]
                      else 0 for i in rows]
        else:
            t = 1
            counts = [int(fed[i] < lengths[i]) for i in rows]
        tokens = np.zeros((len(seqs), t), np.int32)
        valid = np.zeros((len(seqs), t), bool)
        for i, n in enumerate(counts):
            if n:
                cache.ensure_capacity(lanes[i], fed[i] + n)
                tokens[i, :n] = seqs[i][fed[i]:fed[i] + n]
                valid[i, :n] = True
        positions = np.asarray(fed)[:, None] + np.arange(t)
        ctx_lens = [1 if seqs[i] is None else fed[i] + n
                    for i, n in enumerate(counts)]
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            logits, k, v, load = step(
                family, cfg, tree, jnp.asarray(tokens),
                jnp.asarray(positions, jnp.int32), jnp.asarray(valid),
                k, v, jnp.asarray(cache.block_tables[list(lanes)]),
                jnp.asarray(ctx_lens, jnp.int32), load, slots)
        logits = np.asarray(logits)
        for i, n in enumerate(counts):
            got[i].extend(logits[i, :n])
            fed[i] += n
            if n:
                cache.seq_lens[lanes[i]] = fed[i]
        cache.after_commit(lanes[i] for i, n in enumerate(counts) if n)
        slices += 1
    return ([np.stack(row) if row else None for row in got], (k, v), load)
