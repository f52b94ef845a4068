"""Device idle share of the traced slice of the decode cell (device trace)."""

from benchmark.readers import device_idle_pct as read  # noqa: F401
