"""Seconds of set-up in which a worker on the way to the chip was being
leased, made or booted: the union, on the wall clock, of `sched/lease_wait`,
of hostd's `sched/zygote_fork` and `sched/worker_boot` and of the workers'
own `proc/boot`, for the process that holds the chip and, in a serve cell,
the controller's."""

from benchmark import startup


def read(run: dict):
    return startup.worker_boot_s(run)
