"""Percentiles of timed answers, with a missing answer counted as later
than any other."""
from __future__ import annotations

import math
from typing import Optional, Sequence

MISSING = math.inf


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0 < q <= 100): the smallest value
    with at least q% of the values at or below it. A missing answer
    (`MISSING`) sorts above every real one, so if the rank falls on one
    the percentile is `MISSING`. None for no values."""
    if not 0 < q <= 100:
        raise ValueError(f"percentile rank {q} outside (0, 100]")
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies_from_due(due: Sequence[float],
                       done: Sequence[Optional[float]]) -> list:
    """Per answer, the seconds from its due time to its completion;
    `MISSING` where it failed or never completed (done is None)."""
    return [MISSING if d is None else d - t for t, d in zip(due, done)]
