"""Seconds the process that holds the chip spent reaching it: `proc/jax_import`
(jax and what the engine or the train backend imports with it) and
`proc/backend_init` (the first `jax.devices()`: the TPU client), and with
them its `sched/arg_fetch` rows (a task's arguments that took 0.1 s to
unpickle: a replica's constructor is handed the model's configuration, and
unpickling that is what first imports jax)."""

from benchmark import startup


def read(run: dict):
    return startup.backend_init_s(run)
