"""Median per launch of the scheduler's host phases, (launch − assemble) +
(descatter − execute), from the program's chunk spans (repro.obs.trace)."""
from bench.stats import percentile


def read(rec):
    v = rec.get("host_phase_s")
    return None if not v else 1e3 * percentile(v, 50)
