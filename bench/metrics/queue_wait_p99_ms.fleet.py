"""99th percentile of a chunk's wait from submit to launch on its
worker: the reader of queue_wait_p99_ms.serve."""
from bench.harness import load_reader

read = load_reader("queue_wait_p99_ms.serve")
