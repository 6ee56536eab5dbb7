"""Share of device op time spent outside the fused kernel: the engine's
partition gather, per-tile input windows and symbol interleave."""
from bench.readers import glue_share_pct as read  # noqa: F401
