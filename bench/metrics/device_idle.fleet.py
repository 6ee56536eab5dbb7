"""Share of the traced window in which no operation ran on a chip,
averaged over the fleet's chips: the reader of device_idle.serve."""
from bench.readers import device_idle_pct as read  # noqa: F401
