"""Share of the loop's wall time the host spends blocked on the device
(`engine/fetch`), over the timeline's untraced rows: near 0 the cell is
host-bound, whatever a profiler's slice says."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.fetch_wait_pct(run)
