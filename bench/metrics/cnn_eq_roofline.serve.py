"""The fused equalizer kernel's share of its roofline: the least time the
chip could take for the window's useful symbols (max of operations over
the datapath's peak and least bytes over HBM bandwidth, bench/work.py),
over the summed device time of the kernel's events (bench/trace.py)."""
from bench.readers import kernel_roofline_pct as read  # noqa: F401
