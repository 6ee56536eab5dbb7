"""99th percentile, over every chunk due in the window, of the time from
its due time to the emission of what it made emittable; a failed chunk,
or one not emitted by the end, counts as missing. A per-layer reading:
it is set by how many ~0.1 s stalls of the host's device calls land in
the window, and its spread from run to run is wider than any bound."""
from bench.readers import latency_ms


def read(rec):
    return latency_ms(rec, "latency_s", 99)
