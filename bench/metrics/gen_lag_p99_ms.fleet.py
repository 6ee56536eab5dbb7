"""99th percentile of how late the load generator submitted a chunk,
which grows while `submit` waits on the fleet controller's lock: the
reader of gen_lag_p99_ms.serve."""
from bench.harness import load_reader

read = load_reader("gen_lag_p99_ms.serve")
