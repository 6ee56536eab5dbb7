"""The whole window's share of the peak of the fleet's four chips: the
reader of mfu.serve."""
from bench.readers import mfu_pct as read  # noqa: F401
