"""Percentiles timed from the due time, a failed chunk counting as
missing."""
import math

import pytest

from bench import readers
from bench.stats import MISSING, latencies_from_due, percentile


def test_nearest_rank():
    v = list(range(1, 101))
    assert percentile(v, 50) == 50
    assert percentile(v, 99) == 99
    assert percentile(v, 100) == 100
    assert percentile([7.0], 99) == 7.0
    assert percentile([], 50) is None
    with pytest.raises(ValueError):
        percentile(v, 0)


def test_latency_runs_from_due_time_not_submit():
    due = [10.0, 11.0, 12.0]
    done = [10.5, 13.0, 12.25]       # the second was submitted late
    assert latencies_from_due(due, done) == [0.5, 2.0, 0.25]


def test_failed_chunk_counts_as_missing():
    lat = latencies_from_due([0.0, 1.0, 2.0, 3.0],
                             [0.1, None, 2.1, 3.1])
    assert lat[1] == MISSING
    assert percentile(lat, 50) == pytest.approx(0.1)
    assert percentile(lat, 99) == MISSING      # the tail is the missing one


def test_missing_percentile_reads_as_the_wait_to_the_end():
    lat = [0.001] * 98 + [MISSING, MISSING]
    rec = {"latency_s": lat, "missing_s": 61.5}
    assert readers.latency_ms(rec, "latency_s", 50) == pytest.approx(1.0)
    assert readers.latency_ms(rec, "latency_s", 99) == pytest.approx(61500.0)
    assert math.isinf(percentile(lat, 100))
