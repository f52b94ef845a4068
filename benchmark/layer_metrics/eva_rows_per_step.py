"""Rows a T=1 step attended over, all lanes together, inside the window:
`stats()["eva"]` (`rows_attended` over `decode_steps`), read at the window's
two ends."""

from benchmark.eva_flops import rows_per_step as read  # noqa: F401
