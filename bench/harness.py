"""The harness: finds a cell's configuration, traffic mix, driver and
metric readers by name, runs the cell, and builds the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
BENCHMARK.json gives it:

    BENCHMARK.json configs[].file     the configuration (sizes, channel,
                                      formats, deployment)
    bench/traffic/<traffic>.json      the traffic mix; its "driver" key
                                      names the driver module
    bench/drivers/<driver>.py         the driver: `run(ctx) -> record`
    bench/metrics/<metric>.py         one reader: `read(record) -> number
                                      or None`

A later cell, mix or metric is added by adding files and an entry in
BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The cell cannot be run as its files state (a wrong deployment, a
    missing file): the run exits non-zero and prints no result."""


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _entry(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    wl = _entry(bench["workloads"], name, "workload")
    cfg_entry = _entry(bench["configs"], wl["config"], "configuration")
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    return Cell(name=name, chips=int(wl["chips"]),
                config_name=wl["config"], config=config,
                traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_driver(kind: str):
    """bench/drivers/<kind>.py"""
    if not kind.replace("_", "").isalnum():
        raise BenchError(f"bad driver name {kind!r}")
    return importlib.import_module(f"bench.drivers.{kind}")


def load_reader(metric: str,
                bench_dir: pathlib.Path = BENCH_DIR) -> Callable:
    """`read` of bench/metrics/<metric>.py (the name may hold dots)."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '__')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: List[Dict], record: Dict) -> Dict[str, Dict]:
    """Each metric's reader over the run record; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        v = load_reader(m["name"])(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def checks_ok(checks: List[Dict]) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks)


def result_line(cell: Cell, record: Dict, trace: bool) -> Dict[str, Any]:
    """The one JSON object of the contract; `checks` comes last."""
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           record)
    line: Dict[str, Any] = {
        "correct": checks_ok(record["checks"]) and record["failed"] == 0,
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": record["device"],
    }
    if trace and record.get("breakdown"):
        line["breakdown"] = record["breakdown"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in record["checks"]}
    return line


def print_checks(checks: List[Dict], file=sys.stderr) -> None:
    for c in checks:
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=file)


class Window:
    """The measured window of a run: counts compilations inside it and,
    in a traced run, captures the device profile around it (see
    `bench.trace`). `reduce()` after the window fills `reduced` and
    `breakdown`."""

    def __init__(self, ctx: "Ctx"):
        self.ctx = ctx
        self.compiles = 0
        self.reduced: Optional[Dict] = None
        self.breakdown: Optional[Dict] = None
        self._log_dir = str(ctx.work_dir / "trace")
        self._capture = None
        self._ann = None
        self.t_open = 0.0               # perf_counter at the annotation
        self._active = False

    # an XLA compile, or a program loaded from the persistent cache
    COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                      "/jax/compilation_cache/cache_retrieval_time_sec")

    def _on_event(self, event: str, *_a, **_k) -> None:
        if self._active and event in self.COMPILE_EVENTS:
            self.compiles += 1

    def __enter__(self) -> "Window":
        import gc
        import time
        import jax
        from . import trace as trace_lib
        # Set-up leaves ~280k objects (tracing caches, compiled programs)
        # that every full collection walks: a 0.09-0.13 s pause every
        # minute or two on a TPU v5e host. Frozen, a collection in the
        # window walks only what the window allocated.
        gc.freeze()
        self._active = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.ctx.trace:
            self._capture = trace_lib.capture(self._log_dir)
            self._capture.__enter__()
            self._ann = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
            self._ann.__enter__()
        self.t_open = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import gc
        self._active = False
        gc.unfreeze()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._capture.__exit__(*exc)

    def reduce(self, spans: Sequence[Tuple[str, float, float]] = ()) -> None:
        """Reduce the captured trace. `spans` are (label, start, end) on
        the host's perf_counter clock, mapped onto the trace's clock
        through the window annotation, to name idle gaps."""
        if not self.ctx.trace:
            return
        from . import trace as trace_lib
        ex = trace_lib.extract(trace_lib.load(
            trace_lib.find_xplane(self._log_dir)))
        trace_lib.remove(self._log_dir)
        lo, _ = trace_lib.window(ex)
        off = lo - int(self.t_open * 1e9)
        extra = [(n, int(s * 1e9) + off, int(e * 1e9) + off)
                 for n, s, e in spans]
        self.reduced = trace_lib.reduce(ex, extra)
        self.breakdown = {"device_ops": self.reduced["top_ops"],
                          "idle_gaps": self.reduced["idle_gaps"]}


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell, the run's arguments, the devices, and
    `sizes` (overrides of deployment sizes, for tests on the CPU only)."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float
    devices: list
    work_dir: pathlib.Path
    control: bool = False
    sizes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _window: Optional[Window] = None

    def window(self) -> Window:
        self._window = Window(self)
        return self._window

    def limit(self, name: str) -> float:
        return float(self.cell.config["limits"][name])

    def check(self, name: str, value: float) -> Dict:
        return {"name": name, "value": float(value),
                "limit": self.limit(name)}

    def device_info(self) -> Dict[str, Any]:
        d0 = self.devices[0]
        peak = 0
        for d in self.devices:
            try:
                peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
            except (TypeError, KeyError, AttributeError):
                pass
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": len(self.devices), "memory_peak_bytes": peak}


def finish(record: Dict, ctx: Ctx) -> None:
    """Complete a driver's record: the window's own check (nothing
    compiles inside it, limit 0) after the driver's, and busy_s and
    window_s of a traced run in `device`."""
    compiles = ctx._window.compiles if ctx._window is not None else 0
    record["checks"].append({"name": "window_compiles",
                             "value": float(compiles), "limit": 0.0})
    if ctx.trace and record.get("trace"):
        record["device"]["busy_s"] = record["trace"]["busy_s"]
        record["device"]["window_s"] = record["trace"]["window_s"]
