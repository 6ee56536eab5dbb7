"""The harness finds a configuration, a traffic mix and a metric that were
added only as files plus a workload entry, and builds the result line."""
import json
import shutil

import pytest

from bench import harness


@pytest.fixture()
def tree(tmp_path):
    """A copy of BENCHMARK.json and bench/ in a scratch checkout."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    return tmp_path


def test_every_committed_cell_loads():
    bench = harness.load_benchmark()
    for wl in bench["workloads"]:
        cell = harness.load_cell(wl["name"], bench=bench)
        assert cell.chips == wl["chips"]
        assert harness.load_driver(cell.traffic["driver"]).run
        names = [m["name"] for m in cell.end_to_end + cell.per_layer]
        assert "setup_s" in names
        for name in names:
            assert callable(harness.load_reader(name))
        assert set(cell.config["limits"]) >= set(), cell.config


def test_a_cell_added_as_files_only(tree):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    cfg = json.loads((tree / "bench/configs/lp_proakis.json").read_text())
    cfg["name"] = "lp_proakis_30db"
    cfg["channel"]["snr_db"] = 30.0
    (tree / "bench/configs/lp_proakis_30db.json").write_text(json.dumps(cfg))
    mix = json.loads((tree / "bench/traffic/lp_open64.json").read_text())
    mix["rate_syms_per_s"] = 1.0
    (tree / "bench/traffic/lp_trickle.json").write_text(json.dumps(mix))
    (tree / "bench/metrics/chunks_attempted.serve.py").write_text(
        "def read(rec):\n    return rec.get('attempted')\n")
    bench["configs"].append({"name": "lp_proakis_30db", "source": "x",
                             "file": "bench/configs/lp_proakis_30db.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "lp_trickle", "config":
                               "lp_proakis_30db", "traffic": "lp_trickle",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "chunks_attempted.serve",
                               "unit": "chunks", "better": "higher",
                               "source": "host_clock", "layer": "scheduler",
                               "moves": "chunk_p50_ms",
                               "workloads": ["lp_trickle"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "lp_serve" in m["workloads"]:
            m["workloads"].append("lp_trickle")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("lp_trickle", root=tree)
    assert cell.config["channel"]["snr_db"] == 30.0
    assert cell.traffic["rate_syms_per_s"] == 1.0
    assert harness.load_driver(cell.traffic["driver"]).__name__ == \
        "bench.drivers.open_loop"
    names = [m["name"] for m in cell.per_layer]
    assert "chunks_attempted.serve" in names
    read = harness.load_reader("chunks_attempted.serve",
                               bench_dir=tree / "bench")
    assert read({"attempted": 41}) == 41


def test_result_line_leaves_out_what_a_reader_cannot_read():
    cell = harness.load_cell("ht_bulk")
    rec = {"setup_s": 12.5, "symbols": 1000, "window_s": 2.0,
           "attempted": 3, "failed": 0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "topology": cell.config["topology"], "backend": "fused_int8",
           "checks": [{"name": "max_abs_gap", "value": 0.0, "limit": 0.0}]}
    line = harness.result_line(cell, rec, trace=False)
    assert line["metrics"]["syms_per_s"]["value"] == 500.0
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    traced = harness.result_line(cell, rec, trace=True)
    # no trace in the record: only the host-clock share of the peak reads
    assert set(traced["metrics"]) == {"mfu.bulk"}
    rec["checks"][0]["value"] = 1e-3
    assert harness.result_line(cell, rec, trace=False)["correct"] is False


def test_unknown_names_are_errors(tree):
    with pytest.raises(harness.BenchError):
        harness.load_cell("no_such_cell")
    with pytest.raises(harness.BenchError):
        harness.load_reader("no_such_metric")


@pytest.mark.parametrize("compile_inside", [False, True])
def test_a_compile_inside_the_window_fails_the_run(compile_inside, tmp_path):
    import jax
    import jax.numpy as jnp
    cell = harness.load_cell("ht_bulk")
    ctx = harness.Ctx(cell=cell, seed=1, seconds=1.0, trace=False, t0=0.0,
                      devices=jax.devices()[:1], work_dir=tmp_path)
    warm = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(7.0)
    warm(x).block_until_ready()
    with ctx.window():
        warm(x).block_until_ready()
        if compile_inside:
            jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    rec = {"setup_s": 1.0, "symbols": 10, "window_s": 1.0, "attempted": 1,
           "failed": 0, "device": {}, "topology": cell.config["topology"],
           "backend": "fused_int8",
           "checks": [{"name": "max_abs_gap", "value": 0.0, "limit": 0.0}]}
    harness.finish(rec, ctx)
    line = harness.result_line(cell, rec, trace=False)
    compiles = line["checks"]["window_compiles"]
    assert (compiles["value"] >= 1) == compile_inside
    assert compiles["limit"] == 0
    assert line["correct"] is not compile_inside
