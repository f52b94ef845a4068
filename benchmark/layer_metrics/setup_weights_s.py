"""Seconds a replica's engine spent on what it holds on the device before
its first step: `engine/init_params`, `engine/prepare` and `engine/pools`,
each waited for inside its span."""

from benchmark import startup


def read(run: dict):
    return startup.weights_s(run)
