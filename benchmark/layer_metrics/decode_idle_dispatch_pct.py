"""Share of the traced slice in which device 0 is idle while the engine's
thread is inside `engine/dispatch`: from the jitted call to the device's
first operation of the step (launch latency)."""

from benchmark import idle_phases


def read(run: dict):
    return idle_phases.share_pct(run, ("engine/dispatch",))
