"""What the functional LM families (gpt, llama) share.

One implementation of the (init_state, train_step) contract: under a mesh,
params AND optimizer state are sharded (ZeRO-3 via GSPMD propagation
through jit(optimizer.init)) and XLA inserts the collectives; train_step
is jittable with donation.  And one of `serving_params`: the weights as
the cached forward multiplies them, made once (inference/engine.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2, 3))
def _remake(leaves, names, dtype, forms):
    forms = dict(forms)
    return [forms[name](x.astype(dtype)) if name in forms
            else {name: x.astype(dtype)} for x, name in zip(leaves, names)]


def serving_params(params, dtype, cast, forms=None):
    """`params` with every leaf named in `cast` (at any depth) held in
    `dtype`: the rounding the cached forward applies to that leaf at its
    use (`p["wq"].astype(h.dtype)`), done once for all steps instead of
    once per step.  It goes by the leaf's own dtype: one that is already
    in `dtype` comes back as the same array, as does every leaf not
    named, so a tree held in `dtype` (OLMoE's bf16 leaves, a float32
    config) is returned as it is, with no program run and no copy made.
    The rest are made in one compiled program.  `forms` ({name: f}) says
    how a model keeps a leaf it re-makes: `f(cast leaf)` is the dict of
    entries that take the leaf's place (default: itself, under its name)."""
    dtype = jnp.dtype(dtype)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = [path[-1].key for path, _ in flat]
    todo = [i for i, (_, x) in enumerate(flat)
            if names[i] in cast and x.dtype != dtype]
    if not todo:
        return params
    made = dict(zip(todo, _remake(
        [flat[i][1] for i in todo], tuple(names[i] for i in todo), dtype,
        tuple((forms or {}).items()))))
    out = {}
    for i, (path, x) in enumerate(flat):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k.key, {})
        node.update(made.get(i, {names[i]: x}))
    return out


def make_train_step(config, optimizer, mesh, *, init_params, loss_fn,
                    param_specs):
    """`init_params(config, key)`, `loss_fn(params, batch, config, mesh)`,
    `param_specs(config)` define the family; everything else is shared."""
    import optax

    def init_state(key):
        # One compiled program each, not one dispatch per op (building a
        # gpt2-small engine op by op took 55 s on a v5e chip); under a
        # mesh the params are born sharded, never whole on one device.
        shardings = None
        if mesh is not None:
            from ray_tpu.parallel.sharding import (
                shard_opt_state, tree_shardings)
            shardings = tree_shardings(mesh, param_specs(config))
        params = jax.jit(init_params, static_argnums=0,
                         out_shardings=shardings)(config, key)
        opt_state = jax.jit(optimizer.init)(params)
        if mesh is not None:
            opt_state = shard_opt_state(opt_state, params, shardings, mesh)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            state["params"], batch, config, mesh)
        updates, opt_state = optimizer.update(grads, state["opt_state"],
                                              state["params"])
        params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss})

    return init_state, train_step
