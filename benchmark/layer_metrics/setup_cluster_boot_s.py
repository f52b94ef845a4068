"""Seconds of set-up inside `ray_tpu.init()`: the `proc/init` span of the
benchmark's own process (the GCS's and hostd's starts and the driver's
connection are its children), from the program's start-up record."""

from benchmark import startup


def read(run: dict):
    return startup.cluster_boot_s(run)
