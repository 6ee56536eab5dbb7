"""99th percentile of how late the load generator submitted a chunk after
its due time (the benchmark's own generator)."""
from bench.stats import percentile


def read(rec):
    v = rec.get("gen_lag_s")
    return None if not v else 1e3 * percentile(v, 99)
