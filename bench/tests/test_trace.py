"""The reduction from a device trace to busy, idle, kernel and glue time."""
import pathlib

import pytest

from bench import trace

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def test_union_merges_and_clips():
    iv = [(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)]
    assert trace.union(iv, 1, 25) == [(1, 4), (5, 12), (20, 25)]
    assert trace.union([], 0, 10) == []


def test_gaps_are_the_window_less_busy():
    busy = [(1, 4), (5, 12), (20, 25)]
    assert trace.gaps(busy, 0, 30) == [(0, 1), (4, 5), (12, 20), (25, 30)]
    assert trace.gaps([(0, 10)], 0, 10) == []


def test_label_takes_the_innermost_span():
    spans = [("step", 0, 100), ("d2h", 40, 60)]
    assert trace._label(50, spans) == "d2h"
    assert trace._label(10, spans) == "step"
    assert trace._label(200, spans) == "no host span"


def _extract(name):
    import gzip
    import json
    with gzip.open(TESTDATA / f"{name}_v5e.trace.json.gz", "rt") as f:
        return json.load(f)


def test_reduce_a_bulk_trace_recorded_on_the_chip():
    # two steps of ht_bulk on a TPU v5e (extract of the XPlane file)
    r = trace.reduce(_extract("ht_bulk"))
    assert r["window_s"] == pytest.approx(0.02383525)
    assert r["kernel_events"] == 2                 # one kernel a step
    assert r["kernel_s"] == pytest.approx(775.724e-6)
    assert r["busy_s"] == pytest.approx(0.018014507)
    assert r["busy_s"] <= r["kernel_s"] + r["glue_s"] + 1e-12
    assert 0 < r["busy_s"] < r["window_s"]
    top, secs = r["top_ops"][0]
    assert top.endswith(" fusion") and secs == pytest.approx(0.017100139)
    assert any("cnn_eq_fused_int8" in n and n.endswith("custom-call")
               for n, _ in r["top_ops"])
    assert len(r["idle_gaps"]) <= 10
    assert r["idle_gaps"][0][0] == "step"          # host inside the step


def test_reduce_a_serving_trace_recorded_on_the_chip():
    # the first 400 ms of an lp_serve window on a TPU v5e
    ex = _extract("lp_serve")
    r = trace.reduce(ex)
    assert r["window_s"] == pytest.approx(0.4)
    assert r["kernel_events"] == 7
    assert all("cnn_eq_fused_bf16" in n for n, _, _ in
               (o for o in ex["devices"]["/device:TPU:0"]
                if "tpu_custom_call" in o[0]))
    assert 0 < r["busy_s"] < 0.01 * r["window_s"]  # the host sets the pace
    extra = [("launch", ex["devices"]["/device:TPU:0"][0][1] - 10,
              ex["devices"]["/device:TPU:0"][-1][2] + 10)]
    labels = {n for n, _ in trace.reduce(ex, extra)["idle_gaps"]}
    assert labels <= {n for n, _, _ in ex["host"]} | {"launch",
                                                       "no host span"}
