"""The sweep's rule for a sustained rate picks the knee written in the
serving cell's traffic file from the backlogs its sweep recorded."""
import json

import pytest

from bench import harness
from bench.sweep import sustained

# (rate sym/s, backlog in the second fifth, in the last fifth): the
# 30 s sweep on a TPU v5e from which lp_open64.json took its knee
SWEEP = [(132e3, 2.95, 2.0), (176e3, 4.15, 4.15), (220e3, 16.25, 36.2),
         (264e3, 264.0, 948.0)]


@pytest.mark.parametrize("rate,second,last", SWEEP)
def test_sustained_rates_are_those_below_the_knee(rate, second, last):
    mix = json.loads((harness.BENCH_DIR / "traffic" /
                      "lp_open64.json").read_text())
    assert sustained(second, last) == (rate <= mix["knee_syms_per_s"])
