"""The fused equalizer kernel's share of its roofline over the fleet's
chips: the least time for the window's useful symbols over the kernel's
device time summed over every chip; the reader of
cnn_eq_roofline.serve."""
from bench.readers import kernel_roofline_pct as read  # noqa: F401
