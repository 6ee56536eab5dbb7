"""99th percentile of a chunk's wait from submit to launch (the runtime's
Request.wait_s), from the program's chunk spans."""
from bench.stats import percentile


def read(rec):
    v = rec.get("wait_s")
    return None if not v else 1e3 * percentile(v, 99)
