"""repro.obs — unified observability for the serving/fleet/adaptation stack.

One instrumentation spine across every layer built in PRs 1-7:

  * `metrics`  — Counter/Gauge/Histogram with bounded reservoirs behind a
    `MetricsRegistry` of hierarchical dotted names (`serve.launch.latency_s`,
    `fleet.worker0.recovery.replays`, `adapt.shadow.ber`), exported as one
    nested `snapshot()` tree, JSON, or Prometheus text.
  * `trace`    — per-chunk lifecycle spans (submit -> assemble -> launch ->
    execute -> descatter -> emit) with retries/replays/migrations recorded
    as child events, buffered in a bounded ring, exportable as Chrome
    `trace_event` JSON (Perfetto-viewable); `annotate` puts the serving
    stack's host phases on the JAX device profiler's timeline as
    `serve.*` spans.
  * `hub`      — the `Observability` facade (registry + tracer + `Retention`
    policy) that runtimes accept via their `obs=` parameter.
  * `link`     — streaming per-tenant link-quality estimators (decision-
    directed EVM / SNR / symbol-error proxy / confidence histograms) fed
    from the `Session.tap` seam, published as `link.<tenant>.*`.
  * `slo`      — declarative per-tenant `SloRule`s evaluated against the
    registry with hysteresis-latched breach/clear edges, a bounded alert
    ledger in `snapshot()`, and closed-loop hooks (SLO breach → on-demand
    adaptation; promotion resolves the alert).
  * `report`   — `python -m repro.obs.report` console summary from a live
    runtime snapshot or an exported JSON file.

Observation never changes launch order or numerics: spans piggyback on the
existing `ChunkPlan` objects, all hot-path hooks are no-ops when tracing is
off, and the chaos parity tests run bitwise-equal with tracing on.
"""
from .hub import Observability, Retention
from .link import LinkEstimate, LinkMonitor
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, Scope,
                      safe_segment)
from .slo import SloEngine, SloRule
from .trace import PHASES, ChunkSpan, Tracer, annotate

__all__ = [
    "Observability",
    "Retention",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Scope",
    "safe_segment",
    "LinkEstimate",
    "LinkMonitor",
    "SloEngine",
    "SloRule",
    "PHASES",
    "ChunkSpan",
    "Tracer",
    "annotate",
]
