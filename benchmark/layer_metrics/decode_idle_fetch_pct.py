"""Share of the traced slice in which device 0 is idle while the engine's
thread is inside `engine/fetch`: blocked on the step's tokens after the
device has finished, the copy back and the thread's wake-up."""

from benchmark import idle_phases


def read(run: dict):
    return idle_phases.share_pct(run, ("engine/fetch",))
