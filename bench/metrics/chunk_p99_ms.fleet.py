"""99th percentile of the fleet's chunk latencies (recorded; no change
is judged on it): the reader of chunk_p99_ms.serve."""
from bench.harness import load_reader

read = load_reader("chunk_p99_ms.serve")
