"""Seconds from `fit()` or `serve.run()` being called to the leased worker's
first report or the replica being ready: worker boot, the chip lease, jax
reaching the chip and, for a replica, the engine and its weights."""

from __future__ import annotations


def read(run: dict):
    return run.get("worker_ready_s")
