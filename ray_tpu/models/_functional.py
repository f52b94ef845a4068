"""Shared train-step factory for the functional LM families (gpt, llama).

One implementation of the (init_state, train_step) contract: under a mesh,
params AND optimizer state are sharded (ZeRO-3 via GSPMD propagation
through jit(optimizer.init)) and XLA inserts the collectives; train_step
is jittable with donation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
