"""chunk_p50_ms: the median of the same chunk latencies as chunk_p99_ms."""
from bench.readers import latency_ms


def read(rec):
    return latency_ms(rec, "latency_s", 50)
