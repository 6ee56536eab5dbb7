"""Benchmark orchestrator: `PYTHONPATH=src python -m benchmarks.run`.

One benchmark per paper table/figure (see DESIGN.md §6):

    bench_dse       Fig. 2   DSE: CNN vs FIR vs Volterra on IM/DD
    bench_proakis   Fig. 4   the same on the magnetic-recording channel
    bench_quant     Fig. 5/6 3-phase QAT bit-width/BER curves per QLF
    bench_dop       Fig. 8   flexible-DOP study (TPU tile-utilization axis)
    bench_stream    Fig. 9/§7.2  64-instance stream partitioning
    bench_engine    §7       engine backend throughput → BENCH_engine.json
    bench_serve     §5.3     multi-tenant serving → BENCH_serve.json
    bench_adapt     companion papers: online adaptation under drift
                             → BENCH_adapt.json
    bench_fault     robustness: chaos-gated failover → BENCH_fault.json
    bench_fleet     robustness: device-loss migration on a 2-worker fleet
                             → BENCH_fleet.json
    bench_net       wire parity: packetized data+control plane
                             → BENCH_net.json
    bench_link      signal health: link estimators + SLO closed loop
                             → BENCH_link.json
    bench_timing    Fig. 12  timing model vs simulated measurement
    bench_platform  Fig. 13-15  CPU measured / TPU roofline-projected
    bench_roofline  Table 1 / §Roofline  aggregate the dry-run artifacts

`--full` runs paper-scale sweeps (hours); the default is a reduced pass
whose orderings (not absolute BERs) carry the claims.

`--check` is the perf-regression gate: it verifies the docs references
(tools/check_docs.py), then re-measures bench_engine, bench_serve and
bench_adapt (without overwriting the committed baselines) and exits
non-zero if any tracked throughput fell more than `--tol` below the
`BENCH_engine.json` / `BENCH_serve.json` / `BENCH_adapt.json` committed at
the repo root — after normalizing out the
uniform host-speed drift per gate group (geomean over shared keys), so
only RELATIVE per-path regressions fire the gate (default tol: 10% on
accelerators, 35% on interpret-mode CPU hosts — see `_default_tol`). The
adapt, fault and fleet gates additionally enforce HARD,
host-independent criteria: the drift-recovery claim
(`criteria.recovery_ok` in `BENCH_adapt.json`), the chaos-recovery claim
(`criteria.recovery_ok` in `BENCH_fault.json` — bitwise zero-loss
failover under injected faults), the device-loss-migration claim
(`criteria.fleet_recovery_ok` in `BENCH_fleet.json` — a worker killed
mid-stream, every stream migrated bitwise with zero loss and zero
poisoning), and the wire-parity claim (`criteria.net_ok` in
`BENCH_net.json` — symbols served through the packetized
NetIngress→runtime→NetEgress path over a reordering+duplicating
loopback wire stay bitwise vs offline, exactly-once, with the control
plane acking), and the signal-health claim (`criteria.link_ok` in
`BENCH_link.json` — the decision-directed SNR estimate tracks a true
channel SNR ramp, an SLO breach latches during quality degradation and
triggers an event-driven fine-tune whose promotion retires the alert,
and serving with link estimation + SLOs + tracing ON stays bitwise vs
offline on every fused backend) are deterministic under their fixed
seeds, so their failure is never noise. The fault, fleet, net and
link gates carry no throughput rates at all — they are purely the hard
criteria.
Compare like with like: the committed baseline must come from the same
host class AND be recorded in the gate's in-process order
(`--only engine serve adapt fault fleet net link`); CPU hosts run
the kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

from . import (bench_adapt, bench_dop, bench_dse, bench_engine,
               bench_fault, bench_fleet, bench_link, bench_net,
               bench_platform, bench_proakis, bench_quant,
               bench_roofline, bench_serve, bench_stream, bench_timing)
from .common import REPORT_DIR

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from tools import check_docs  # noqa: E402  (repo-root import, no package)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _engine_rates(rep: dict) -> dict:
    return {f"engine/{c}/{b}": r
            for c, e in rep.get("configs", {}).items()
            for b, r in e.get("syms_per_s", {}).items()}


def _serve_rates(rep: dict) -> dict:
    return {f"serve/{c}/N{n}": t["serve"]["agg_syms_per_s"]
            for c, e in rep.get("configs", {}).items()
            for n, t in e.get("tenants", {}).items()}


def _adapt_rates(rep: dict) -> dict:
    ov = rep.get("overhead", {})
    return {f"adapt/{k}": ov[k]
            for k in ("serve_syms_per_s_frozen", "serve_syms_per_s_adapting")
            if k in ov}


def _adapt_criteria(rep: dict):
    """Hard (host-independent) gate on the fresh adapt report: the BER
    drift-recovery criterion is deterministic under its fixed seeds, so a
    failure is a code regression, never noise."""
    crit = rep.get("criteria", {})
    if crit.get("recovery_ok", False):
        return []
    return [f"adapt: drift-recovery criterion failed "
            f"(frozen degradation {crit.get('frozen_degradation_x', 0):.1f}x"
            f" must be >= 4, adaptive-vs-fresh "
            f"{crit.get('adaptive_vs_fresh_x', 99):.2f}x must be <= 2)"]


def _fault_rates(rep: dict) -> dict:
    """The fault gate tracks NO throughput rates — recovery latencies are
    host-speed dependent; the whole gate is the hard criterion below."""
    return {}


def _fault_criteria(rep: dict):
    """Hard (host-independent) gate on the fresh fault report: under the
    injected faults every chunk must be emitted exactly once, bitwise-equal
    to offline, with no sessions poisoned and every fault fired.
    Deterministic under its fixed seeds — a failure is a code regression,
    never noise."""
    crit = rep.get("criteria", {})
    if crit.get("recovery_ok", False):
        return []
    return [f"fault: chaos-recovery criterion failed "
            f"(zero_loss={crit.get('zero_loss')} "
            f"bitwise={crit.get('bitwise')} "
            f"sessions_poisoned={crit.get('sessions_poisoned')} "
            f"faults_fired={crit.get('faults_fired')})"]


def _fleet_rates(rep: dict) -> dict:
    """The fleet gate tracks NO throughput rates — migration latencies are
    host-speed dependent; the whole gate is the hard criterion below."""
    return {}


def _fleet_criteria(rep: dict):
    """Hard (host-independent) gate on the fresh fleet report: a worker
    killed mid-stream, and still every chunk emitted exactly once,
    bitwise-equal to offline (contract #10, placement invariance), zero
    sessions poisoned, both device faults fired. Deterministic under its
    fixed seeds — a failure is a code regression, never noise."""
    crit = rep.get("criteria", {})
    if crit.get("fleet_recovery_ok", False):
        return []
    return [f"fleet: device-loss-migration criterion failed "
            f"(zero_loss={crit.get('zero_loss')} "
            f"bitwise={crit.get('bitwise')} "
            f"sessions_poisoned={crit.get('sessions_poisoned')} "
            f"device_faults_fired={crit.get('device_faults_fired')})"]


def _net_rates(rep: dict) -> dict:
    """The net gate tracks NO throughput rates — framed syms/s is
    host-speed dependent; the whole gate is the hard criterion below."""
    return {}


def _net_criteria(rep: dict):
    """Hard (host-independent) gate on the fresh net report: symbols
    served through the packetized wire (control-plane open, DATA frames
    in, symbol frames out) over a seeded reordering+duplicating loopback
    must stay bitwise vs offline and exactly-once, with the impairments
    verifiably fired and every control command acked. Deterministic
    under its fixed seeds — a failure is a code regression, never
    noise."""
    crit = rep.get("criteria", {})
    if crit.get("net_ok", False):
        return []
    return [f"net: wire-parity criterion failed "
            f"(bitwise={crit.get('bitwise')} "
            f"exactly_once={crit.get('exactly_once')} "
            f"impairments_fired={crit.get('impairments_fired')} "
            f"control_ok={crit.get('control_ok')})"]


def _link_rates(rep: dict) -> dict:
    """The link gate tracks NO throughput rates — estimation is host-side
    numpy; the whole gate is the hard criterion below."""
    return {}


def _link_criteria(rep: dict):
    """Hard (host-independent) gate on the fresh link report: the
    decision-directed SNR estimate must track the true channel SNR ramp,
    the SLO breach must latch during the degradation and trigger the
    event-driven fine-tune, the promotion must retire the alert, and
    serving with link + SLO + tracing ON must stay bitwise vs offline on
    every fused backend. Deterministic under its fixed seeds — a failure
    is a code regression, never noise."""
    crit = rep.get("criteria", {})
    if crit.get("link_ok", False):
        return []
    return [f"link: signal-health criterion failed "
            f"(snr_corr={crit.get('snr_corr', 0.0):.2f} "
            f"drop={crit.get('snr_est_drop_db', 0.0):.2f}dB "
            f"breach_fired={crit.get('breach_fired')} "
            f"promoted={crit.get('promoted')} "
            f"resolved={crit.get('resolved')} "
            f"final_clear={crit.get('final_clear')} "
            f"bitwise={crit.get('bitwise')})"]


def _default_tol() -> float:
    """Host-class-aware gate width. Real accelerators get the tight 10%
    gate; interpret-mode CPU hosts run the kernels ~50× slower with
    ±25–40% per-key noise even after drift normalization (see
    docs/ARCHITECTURE.md), where a 10% gate fires on noise in most clean
    runs — the honest per-key bound there is 35%, and serve-vs-sequential
    RATIOS carry the fine-grained regression signal instead."""
    import jax
    return 0.10 if jax.default_backend() != "cpu" else 0.35


def _geomean(vals) -> float:
    vals = [v for v in vals if v > 0]
    if not vals:
        return 1.0
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def check(tol: float | None = None) -> int:
    """Regress fresh engine/serve throughput against committed baselines.

    Rates are compared DRIFT-NORMALIZED: within each gate group (engine,
    serve) both the fresh and the baseline rates are divided by their
    geometric mean over the shared keys, so a uniform host-speed change
    (this host drifts up to 2× over minutes; a TPU pool may simply be a
    different machine) cancels and the gate fires only when one path
    regressed RELATIVE to the others. The raw drift factor is printed so a
    genuinely slower build still leaves a visible trace. The gate is
    >`tol` below baseline on any normalized rate (default: 10% on
    accelerator hosts, 35% on interpret-mode CPU hosts — see
    `_default_tol`), and a regression must REPRODUCE: suspect groups are
    re-measured once and only keys regressed in both passes fail (noise
    spikes don't repeat; real regressions do). Methodology and
    interpret-mode caveats in docs/ARCHITECTURE.md "Benchmarks and the
    regression gate".
    Also runs the docs reference check (tools/check_docs.py) first — stale
    docs fail the same gate as stale baselines. On failure, every
    regressed key is listed with its fresh rate, baseline rate, and the
    normalized drop.
    """
    if tol is None:
        tol = _default_tol()
        print(f"[check] tolerance {tol:.0%} (host-class default; "
              f"override with --tol)")
    doc_rc = check_docs.main([])
    if doc_rc != 0:
        print("[check] FAIL: docs reference check (see above); "
              "fix docs/*.md before measuring perf")
        return doc_rc
    gates = (
        ("engine", REPO_ROOT / "BENCH_engine.json",
         lambda: bench_engine.run(out_path=None), _engine_rates, None),
        ("serve", REPO_ROOT / "BENCH_serve.json",
         lambda: bench_serve.run(out_path=None), _serve_rates, None),
        ("adapt", REPO_ROOT / "BENCH_adapt.json",
         lambda: bench_adapt.run(out_path=None), _adapt_rates,
         _adapt_criteria),
        ("fault", REPO_ROOT / "BENCH_fault.json",
         lambda: bench_fault.run(out_path=None), _fault_rates,
         _fault_criteria),
        ("fleet", REPO_ROOT / "BENCH_fleet.json",
         lambda: bench_fleet.run(out_path=None), _fleet_rates,
         _fleet_criteria),
        ("net", REPO_ROOT / "BENCH_net.json",
         lambda: bench_net.run(out_path=None), _net_rates,
         _net_criteria),
        ("link", REPO_ROOT / "BENCH_link.json",
         lambda: bench_link.run(out_path=None), _link_rates,
         _link_criteria))
    # validate the configuration before burning minutes of re-measurement
    missing = [p.name for _, p, _, _, _ in gates if not p.exists()]
    if missing:
        print(f"[check] FAIL: no committed baseline(s): {', '.join(missing)}")
        return 2
    def _normalized_ratios(baseline, fresh, label):
        """Per-key fresh/baseline ratios with the group's uniform
        host-speed drift (geomean over shared keys) divided out."""
        shared = [k for k in sorted(baseline) if k in fresh]
        if not shared:
            return {}
        drift = (_geomean(fresh[k] for k in shared)
                 / _geomean(baseline[k] for k in shared))
        print(f"[check] {label}: host-speed drift vs baseline {drift:.2f}x "
              f"(normalized out of the per-key gate)")
        return {k: fresh[k] / baseline[k] / drift for k in shared}

    failures = []          # (key, fresh, baseline, normalized ratio)
    hard_failures = []     # host-independent criteria (e.g. BER recovery)
    compared = 0
    for name, path, bench_fn, extract, criteria_fn in gates:
        baseline = extract(json.loads(path.read_text()))
        fresh_report = bench_fn()["results"]["report"]
        fresh = extract(fresh_report)
        if criteria_fn is not None:
            for msg in criteria_fn(fresh_report):
                print(f"[check] CRITERION FAILED: {msg}")
                hard_failures.append(msg)
        for key in sorted(baseline):
            if key not in fresh:
                print(f"[check] warn: {key} in baseline but not re-measured")
        ratios = _normalized_ratios(baseline, fresh, name)
        suspects = {k: r for k, r in ratios.items() if r < 1.0 - tol}
        if suspects:
            # a real regression reproduces; a noise spike (this host's CPU
            # allocation varies over seconds) almost never does twice — so
            # fail only keys that regress in BOTH of two measurements
            print(f"[check] {name}: {len(suspects)} suspect(s) "
                  f"{sorted(suspects)} — re-measuring to confirm")
            fresh2 = extract(bench_fn()["results"]["report"])
            ratios2 = _normalized_ratios(baseline, fresh2, f"{name}#2")
            for k in list(suspects):
                if ratios2.get(k, 0.0) >= 1.0 - tol:
                    print(f"[check] {name}: {k} recovered on re-measure "
                          f"({ratios2.get(k, 0.0):.2f}x) — noise, not gated")
                    ratios[k] = ratios2[k]
                else:
                    ratios[k] = max(suspects[k], ratios2.get(k, 0.0))
        for key, ratio in ratios.items():
            compared += 1
            status = "ok" if ratio >= 1.0 - tol else "REGRESSION"
            print(f"[check] {status}: {key} {fresh[key]:,.0f} vs baseline "
                  f"{baseline[key]:,.0f} sym/s ({ratio:.2f}x normalized)")
            if ratio < 1.0 - tol:
                failures.append((key, fresh[key], baseline[key], ratio))
    print(f"[check] {compared} rates compared, {len(failures)} regressions, "
          f"{len(hard_failures)} hard-criterion failure(s)")
    if failures:
        print(f"[check] FAIL — rates more than {tol:.0%} below baseline "
              f"after drift normalization:")
        for key, f, b, r in failures:
            print(f"[check]   {key}: {f:,.0f} sym/s vs baseline {b:,.0f} "
                  f"sym/s — {(1.0 - r):.1%} relative drop "
                  f"(allowed {tol:.0%})")
        print("[check] interpret-mode CPU hosts are noisy (±25–40% per "
              "key); if this host class matches the baseline, re-run or "
              "raise --tol (see docs/ARCHITECTURE.md)")
    if hard_failures:
        print("[check] FAIL — host-independent criteria (deterministic, "
              "not noise-gated):")
        for msg in hard_failures:
            print(f"[check]   {msg}")
    return 1 if (failures or hard_failures) else 0


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (hours)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--check", action="store_true",
                    help="re-measure engine/serve throughput and fail on "
                         ">tol regression vs the committed BENCH_*.json")
    ap.add_argument("--tol", type=float, default=None,
                    help="--check regression tolerance (fraction; default "
                         "0.10 on accelerators, 0.35 on interpret-mode CPU "
                         "hosts; raise on noisy shared hosts)")
    args = ap.parse_args(argv)

    if args.check:
        return check(tol=args.tol)

    steps = 700 if not args.full else 10_000
    jobs = [
        ("timing", lambda: bench_timing.run()),
        ("engine", lambda: bench_engine.run()),
        ("serve", lambda: bench_serve.run()),
        ("adapt", lambda: bench_adapt.run()),
        ("fault", lambda: bench_fault.run()),
        ("fleet", lambda: bench_fleet.run()),
        ("net", lambda: bench_net.run()),
        ("link", lambda: bench_link.run()),
        ("stream", lambda: bench_stream.run()),
        ("dop", lambda: bench_dop.run()),
        ("roofline", lambda: bench_roofline.run()),
        ("platform", lambda: bench_platform.run()),
        ("proakis", lambda: bench_proakis.run(steps=min(steps, 800))),
        ("quant", lambda: bench_quant.run(steps=min(steps, 600))),
        ("dse", lambda: bench_dse.run(full=args.full, steps=steps)),
    ]
    if args.only:
        jobs = [(n, f) for n, f in jobs if n in args.only]

    t0 = time.time()
    failures = []
    summary = {}
    for name, fn in jobs:
        print(f"\n=== bench:{name} " + "=" * 50)
        try:
            out = fn()
            summary[name] = {"status": "ok",
                             "elapsed_s": out.get("elapsed_s")}
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            summary[name] = {"status": f"failed: {e}"}
    summary["total_elapsed_s"] = round(time.time() - t0, 1)
    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    (REPORT_DIR / "benchmarks_summary.json").write_text(
        json.dumps(summary, indent=2))
    print("\n=== benchmark summary ===")
    for k, v in summary.items():
        print(f"  {k}: {v}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
