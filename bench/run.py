#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (configuration, traffic mix, chips) is the workload of that name
in BENCHMARK.json. One process holds the chip(s): it makes the inputs and
weights from the seed, warms up every shape the cell uses, measures for
`--seconds`, compares the window's answers with the plain reference, and
prints as its last stdout line one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1`
its per-layer metrics), `device`, with `--trace 1` a `breakdown`, and
last `checks`, each compared number beside its limit (also the last lines
on stderr). Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result. `--control 1` puts the reference, computed
one precision below the configuration's, in the program's place: its run
must come out not correct.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a crash of the process prints every thread's stack on stderr; no
    # watchdog dumps stacks of running threads, which can itself crash
    faulthandler.enable()

    from bench import harness
    cell = harness.load_cell(args.workload)
    driver = harness.load_driver(cell.traffic["driver"])

    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the cell goes to the persistent cache, however fast
    # it compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run: no TPU (found {devices[0].platform}); the benchmark "
              f"does not fall back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from bench.peaks import peaks
    peaks(devices[0].device_kind)          # an unknown chip is an error

    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t0=T0,
                      devices=devices[:cell.chips],
                      work_dir=ROOT / ".bench_work",
                      control=bool(args.control))
    record = driver.run(ctx)
    harness.finish(record, ctx)
    line = harness.result_line(cell, record, ctx.trace)
    for note in record.get("notes", ()):
        print(f"note: {note}", file=sys.stderr)
    harness.print_checks(record["checks"])
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
