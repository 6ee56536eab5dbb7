"""The two per-chip readers of the `lp_fleet4` cell,
`chip_busy_spread.fleet` and `glue_spread.fleet`, on hand-made run
records: the busiest chip (by busy seconds, or by non-kernel op seconds)
over the mean of the chips the cell used."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench.harness import load_reader  # noqa: E402

METRICS = ("chip_busy_spread.fleet", "glue_spread.fleet")
KEY = {"chip_busy_spread.fleet": "busy_s", "glue_spread.fleet": "glue_s"}


def _record(per_chip, key, count=None):
    devices = [{"plane": f"/device:TPU:{i}", "busy_s": 0.0, "glue_s": 0.0,
                "kernel_s": 0.0, "kernel_events": 0, key: v}
               for i, v in enumerate(per_chip)]
    return {"device": {"count": len(per_chip) if count is None else count},
            "trace": {"devices": devices}}


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("per_chip,want", [
    ([0.25, 0.25, 0.25, 0.25], 1.0),          # even placement
    ([1.0, 0.0, 0.0, 0.0], 4.0),              # all the work on one chip
    ([0.3, 0.1, 0.1, 0.1], 2.0),
])
def test_spread_of_hand_made_chips(metric, per_chip, want):
    got = load_reader(metric)(_record(per_chip, KEY[metric]))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_a_chip_without_an_op_line_counts_as_idle(metric):
    # trace.extract drops a plane with no op line: three planes of four
    rec = _record([0.2, 0.2, 0.2], KEY[metric], count=4)
    assert load_reader(metric)(rec) == pytest.approx(4 / 3)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("rec", [
    {"device": {"count": 4}},                          # untraced run
    {"device": {"count": 4}, "trace": None},
    {"device": {"count": 4}, "trace": {"devices": []}},
])
def test_no_trace_reads_none(metric, rec):
    assert load_reader(metric)(rec) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_work_on_any_chip_reads_none(metric):
    assert load_reader(metric)(_record([0.0] * 4, KEY[metric])) is None
