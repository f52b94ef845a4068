"""Device idle share of the traced slice of the chat cell (device trace)."""

from benchmark.readers import device_idle_pct as read  # noqa: F401
