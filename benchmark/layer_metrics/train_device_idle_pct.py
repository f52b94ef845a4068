"""Device idle share of the traced train steps (device trace)."""

from benchmark.readers import device_idle_pct as read  # noqa: F401
