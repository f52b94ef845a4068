"""Share of device 0's idle time (gaps of 2 us and more) that some
`engine/<phase>` annotation of the program covers: the check that the three
`decode_idle_*_pct` shares still see the engine's loop after it is
restructured."""

from benchmark.idle_phases import attributed_pct as read  # noqa: F401
