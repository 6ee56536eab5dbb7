#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: offer a list of fixed rates, one after
another, to one set-up runtime, and report for each whether the backlog
grew. Run once on the chip when a serving cell is defined; the knee it
finds is written, as a number, into the cell's traffic file.

    python3 bench/sweep.py --workload lp_serve --seed 5 --seconds 30 \
        --rates 4e6,8e6,12e6

Per rate it prints one JSON line: chunk latency p50/p99 (ms, from due
time), generator lag p99 and max (ms), missing chunks, and the backlog
(chunks due and not yet landed) averaged over the window's second and
last fifths. A rate is sustained when its backlog does not grow: the
last fifth's is at most the second fifth's plus `SLACK_CHUNKS`. The knee
is the highest sustained rate.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# Poisson arrivals move a steady backlog by a few chunks between fifths
SLACK_CHUNKS = 8


def sustained(second: float, last: float) -> bool:
    """The backlog (chunks) of the last fifth did not grow past the
    second fifth's."""
    return bool(last <= second + SLACK_CHUNKS)


def backlog(due, done, t):
    import numpy as np
    d = np.asarray(due)
    e = np.asarray([np.inf if x is None else x for x in done])
    return float(np.sum(d <= t) - np.sum(e <= t))


def summarize(served, t0, seconds):
    import numpy as np
    from bench.stats import MISSING, latencies_from_due, percentile
    lat = latencies_from_due(served.due, [None if f else d for d, f in
                                          zip(served.done, served.failed)])
    fifth = seconds / 5.0
    second = np.mean([backlog(served.due, served.done, t0 + fifth + x)
                      for x in np.linspace(0, fifth, 20)])
    last = np.mean([backlog(served.due, served.done, t0 + 4 * fifth + x)
                    for x in np.linspace(0, fifth, 20)])
    lag = np.asarray(served.lag)
    p = [percentile(lat, q) for q in (50, 99)]
    return {
        "chunks": len(lat),
        "missing": sum(1 for v in lat if v == MISSING),
        "p50_ms": None if p[0] == MISSING else 1e3 * p[0],
        "p99_ms": None if p[1] == MISSING else 1e3 * p[1],
        "lag_p99_ms": 1e3 * float(np.percentile(lag, 99)),
        "lag_max_ms": 1e3 * float(lag.max()),
        "backlog_second_fifth": second,
        "backlog_last_fifth": last,
        "sustained": sustained(second, last),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated symbols per second")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.drivers import open_loop
    cell = harness.load_cell(args.workload)
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: needs the cell's chips", file=sys.stderr)
        return 2
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, t0=T0, devices=devices[:cell.chips],
                      work_dir=ROOT / ".bench_work")
    st = open_loop.State(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - T0}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            sched = open_loop.Schedule(cell.traffic, rate, st.n,
                                       args.seconds, args.seed, st.n_os)
            t0 = time.perf_counter()
            served = st.measure(sched, float(cell.traffic["grace_s"]))
            out = {"rate_syms_per_s": rate}
            out.update(summarize(served, t0, args.seconds))
            print(json.dumps(out), flush=True)
    finally:
        st.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
