"""Share of the engine thread's wall time in admit, build_batch, dispatch
and commit during which it held no core: 100 x (`cpu_wall_s` - `cpu_s`) /
`cpu_wall_s` over the iterations that read the thread's CPU clock (one in
four, drawn; `cpu_steps` of them), in the seconds of the window's timeline
that the profiler's session did not touch; not measured where fewer than
`step_parts.MIN_CLOCKED` such iterations fell in."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.host_offcpu_pct(run)
