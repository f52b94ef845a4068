"""Share of the traced slice in which device 0 is idle while the engine's
thread commits the last step's tokens, admits requests or builds the next
batch (`engine/commit`, `engine/admit`, `engine/build_batch`): serial host
work between two launches."""

from benchmark import idle_phases


def read(run: dict):
    return idle_phases.share_pct(run, idle_phases.HOST_PHASES)
