#!/usr/bin/env python3
"""Smoke run of the equalizer's main path on a TPU, through its entry points.

    python3 chip_smoke.py             # one chip: engine + serve phases
    python3 chip_smoke.py --chips 4   # four chips: halo mesh + fleet phases

One chip, at the high-throughput (HT) operating point's deployment size
(64 instances × 7,320 symbols, `repro.configs.equalizer_ht`), random weights
from `--seed`:

  * engine — one `EqualizerEngine` per fused backend (fp32; bf16 and int8
    from QAT formats) equalizes the (64, 14,640)-sample batch in one call.
    The compiled program must hold the Pallas kernel (`tpu_custom_call`),
    and the output must keep its oracle contract against the pure-jnp
    oracle run on the host CPU device: int8 exact, bf16 bitwise, fp32
    within 5e-6 (~2 ULP at the output's magnitudes);
  * serve — `AsyncServeRuntime` streams chunked traffic for 8 int8-HT and
    8 bf16-LP tenants; every tenant's streamed output must equal the offline
    engine bitwise (contract #4).

Four chips (`--chips 4`), only the cross-chip paths and their references:

  * halo — `halo_apply_batched` over a 4-chip mesh (N_i instances as mesh
    devices, `ppermute` halos) against the one-chip engine on the same
    streams: int8 bitwise, fp32 within 5e-6, on the interior;
  * fleet — a 4-worker `FleetRuntime`: each worker's launches must land on
    its own chip; then `FaultPlan` kills worker 1 and its migrated streams
    must stay bitwise-equal to offline with every chunk emitted once.

Timings printed on the way are smoke timings (a few warm calls ended with
`block_until_ready`), not benchmark numbers. The last line of stdout is
`{"ok": true, "device": {...}}`; any failed check raises, so the exit code
is non-zero and that line is never printed. Without a TPU it exits 2.
One process holds the chip: nothing here starts a child that touches JAX.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.channels import imdd, proakis  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import equalizer_ht as HT  # noqa: E402
from repro.configs import equalizer_lp as LP  # noqa: E402
from repro.core import equalizer as eq  # noqa: E402
from repro.core import stream_partition as sp  # noqa: E402
from repro.core.engine import EqualizerEngine  # noqa: E402
from repro.kernels.cnn_eq import ref  # noqa: E402
from repro.kernels.cnn_eq.cnn_eq import resolve_interpret  # noqa: E402
from repro.parallel import halo  # noqa: E402
from repro.serve import (AsyncServeRuntime, BatchPolicy, Fault,  # noqa: E402
                         FaultPlan, FleetRuntime, TenantSpec, chop, replay)

TILE_M = 128                  # one lane-width of final positions per tile
FP32_TOL = 5e-6               # the fp32 kernel-vs-oracle contract
# QAT formats whose deployment ladder picks each backend (as in
# examples/serve_equalizer.py): 8-bit → fused_int8, 12-bit → fused_bf16
QAT = {
    "fused_int8": {"w_int": 2, "w_frac": 5, "a_int": 3, "a_frac": 4},
    "fused_bf16": {"w_int": 3, "w_frac": 8, "a_int": 3, "a_frac": 8},
    "fused_fp32": None,
}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _params(cfg, backend: str, seed: int):
    params = eq.init(jax.random.PRNGKey(seed), cfg)
    if QAT[backend] is not None:
        params["qat"] = {f"layer{i}": {k: jnp.asarray(float(v))
                                       for k, v in QAT[backend].items()}
                         for i in range(cfg.layers)}
    return params


def build_engine(cfg, backend: str, seed: int) -> EqualizerEngine:
    engine = EqualizerEngine.from_params(
        _params(cfg, backend, seed), eq.init_bn_state(cfg), cfg,
        backend="auto", tile_m=TILE_M)
    check(engine.backend == backend,
          f"QAT formats deployed {engine.backend}, expected {backend}")
    return engine


def require_pallas_kernel(engine: EqualizerEngine, compiled) -> None:
    """The launch is the compiled Pallas kernel, not interpret mode or the
    reference."""
    check(not resolve_interpret(engine.interpret),
          f"{engine.backend}: interpret mode resolved on")
    check("tpu_custom_call" in compiled.as_text(),
          f"{engine.backend}: no Pallas kernel in the compiled program")


def warm_timing(fn, *args, reps: int = 5) -> float:
    """Best of `reps` warm calls, each ended with block_until_ready."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def oracle_on_host(engine: EqualizerEngine, x: np.ndarray) -> np.ndarray:
    """The backend's pure-jnp oracle, run on the host CPU device so the
    chip's matmul precision cannot hide a fault."""
    cpu = jax.devices("cpu")[0]
    strides = eq.layer_strides(engine.cfg)
    w = jax.device_put(engine.weights, cpu)
    if engine.backend == "fused_int8":
        fn = lambda v, w: ref.cnn_eq_quant(v, w, strides, engine.formats)
    elif engine.backend == "fused_bf16":
        fn = lambda v, w: ref.cnn_eq_bf16(v, w, strides)
    else:
        fn = lambda v, w: ref.cnn_eq(v, w, strides)
    with jax.default_device(cpu):
        return np.asarray(jax.jit(fn)(jax.device_put(x, cpu), w))


def compare(backend: str, got: np.ndarray, want: np.ndarray) -> str:
    check(got.shape == want.shape, f"{backend}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{backend}: non-finite output")
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    if backend == "fused_fp32":
        check(err <= FP32_TOL, f"{backend}: max |Δ| {err:.3e} > {FP32_TOL}")
        return (f"max |Δ| {err:.3e}, {int(np.sum(got != want))} of "
                f"{got.size} outputs differ, max |y| {np.max(np.abs(want)):.3f}")
    check(bool(np.array_equal(got, want)),
          f"{backend}: not bitwise-equal (max |Δ| {err:.3e})")
    return "bitwise equal"


def simulate(sim, channel, n_syms: int, seed: int) -> np.ndarray:
    """A received waveform, simulated in bulk on the host CPU device (input
    set-up, not part of the path under test)."""
    with jax.default_device(jax.devices("cpu")[0]):
        rx, _ = sim(jax.random.PRNGKey(seed), channel, n_syms)
        return np.asarray(rx, np.float32)


def ht_streams(n_streams: int, n_syms: int, seed: int) -> np.ndarray:
    """n_streams consecutive windows of one simulated 40 GBd IM/DD stream."""
    rx = simulate(imdd.simulate, HT.CHANNEL, n_streams * n_syms, seed)
    return rx.reshape(n_streams, n_syms * HT.CNN.n_os)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def engine_phase(seed: int, n_instances: int = HT.N_INSTANCES,
                 l_inst: int = HT.L_INST) -> None:
    t0 = time.perf_counter()
    x = ht_streams(n_instances, l_inst, seed)
    x_dev = jnp.asarray(x)
    print(f"engine inputs: {x.shape} samples simulated in "
          f"{time.perf_counter() - t0:.2f} s")
    for i, backend in enumerate(("fused_fp32", "fused_bf16", "fused_int8")):
        engine = build_engine(HT.CNN, backend, seed + 1 + i)
        t0 = time.perf_counter()
        compiled = jax.jit(lambda v, e=engine: e(v)).lower(x_dev).compile()
        t_compile = time.perf_counter() - t0
        require_pallas_kernel(engine, compiled)
        got = np.asarray(compiled(x_dev))
        t0 = time.perf_counter()
        want = oracle_on_host(engine, x)
        t_oracle = time.perf_counter() - t0
        verdict = compare(backend, got, want)
        t = warm_timing(compiled, x_dev)
        print(f"engine {backend}: ({n_instances}, {x.shape[1]}) samples, "
              f"compile {t_compile:.2f} s, host oracle {t_oracle:.2f} s; "
              f"smoke timing {t * 1e3:.3f} ms per call "
              f"({got.size / t:.4g} sym/s); vs oracle: {verdict}")


def _tenant(op: str, idx: int, n_syms: int, seed: int):
    """An int8-HT or a bf16-LP tenant with its simulated waveform."""
    backend, cfg, sim, channel = (
        ("fused_int8", HT.CNN, imdd.simulate, HT.CHANNEL) if op == "ht"
        else ("fused_bf16", LP.CNN, proakis.simulate, LP.CHANNEL))
    key_seed = seed + 100 * idx + (0 if op == "ht" else 1)
    spec = TenantSpec(f"{op}-{idx}", cfg, params=_params(cfg, backend,
                                                         key_seed),
                      bn_state=eq.init_bn_state(cfg), backend="auto",
                      tile_m=TILE_M)
    return spec, simulate(sim, channel, n_syms, key_seed)


def _check_streams(rt, tenants, what: str) -> None:
    """Every tenant's output equals the offline engine bitwise, with every
    symbol emitted exactly once."""
    for spec, wave in tenants:
        got = rt.output(spec.tenant_id)
        want = np.asarray(spec.build_engine()(jnp.asarray(wave[None])))[0]
        check(got.shape == want.shape,
              f"{what} {spec.tenant_id}: emitted {got.shape}, offline "
              f"{want.shape}")
        check(bool(np.array_equal(got, want)),
              f"{what} {spec.tenant_id}: streamed != offline")


def serve_phase(seed: int, per_op: int = 8, n_syms: int = 16384,
                chunk_syms: int = 1024) -> None:
    tenants = [_tenant(op, i, n_syms, seed)
               for op in ("ht", "lp") for i in range(per_op)]
    rt = AsyncServeRuntime(BatchPolicy(max_batch=per_op, max_wait_s=1e9))
    try:
        for spec, _ in tenants:
            backend = rt.open(spec).engine.backend
            check(backend == ("fused_int8" if spec.tenant_id.startswith("ht")
                              else "fused_bf16"),
                  f"{spec.tenant_id} deployed {backend}")
        streams = {spec.tenant_id: chop(w, chunk_syms * spec.cfg.n_os,
                                        seed=i, jitter=0.5)
                   for i, (spec, w) in enumerate(tenants)}
        rep = replay(rt, streams)
        _check_streams(rt, tenants, "serve")
        st = rt.stats()
    finally:
        rt.shutdown()
    print(f"serve: {len(tenants)} tenants (int8-HT + bf16-LP), "
          f"{rep['total_syms']} symbols, {st['launches']} launches, "
          f"mean batch {st['mean_batch']:.2f}; smoke timing "
          f"{rep['elapsed_s']:.3f} s incl. compiles "
          f"({rep['agg_syms_per_s']:.4g} sym/s); streamed == offline "
          f"bitwise for every tenant")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def halo_phase(seed: int, devices, n_rows: int = 16,
               l_inst: int = HT.L_INST) -> None:
    n_inst = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    x = ht_streams(n_rows, n_inst * l_inst, seed)
    x_one = jax.device_put(x, devices[0])
    x_mesh = jax.device_put(x, NamedSharding(mesh, P(None, "data")))
    o = sp.overlap_symbols(HT.CNN)
    for i, backend in enumerate(("fused_fp32", "fused_int8")):
        engine = build_engine(HT.CNN, backend, seed + 1 + i)
        t0 = time.perf_counter()
        sharded = jax.jit(lambda v, e=engine: halo.halo_apply_batched(
            e, v, HT.CNN, mesh)).lower(x_mesh).compile()
        t_compile = time.perf_counter() - t0
        require_pallas_kernel(engine, sharded)
        check("collective-permute" in sharded.as_text(),
              f"halo {backend}: no ppermute in the compiled program")
        got = np.asarray(sharded(x_mesh))[:, o:-o]
        want = np.asarray(engine(x_one))[:, o:-o]
        verdict = compare(backend, got, want)
        t = warm_timing(sharded, x_mesh)
        print(f"halo {backend}: ({n_rows}, {x.shape[1]}) samples over "
              f"{n_inst} chips, compile {t_compile:.2f} s; smoke timing "
              f"{t * 1e3:.3f} ms per call; vs one-chip engine (interior): "
              f"{verdict}")


def fleet_phase(seed: int, devices, per_op: int = 4, n_syms: int = 8192,
                chunk_syms: int = 512, kill: int = 1) -> None:
    n_workers = len(devices)
    tenants = [_tenant(op, i, n_syms, seed)
               for op in ("ht", "lp") for i in range(per_op)]
    fp = FaultPlan([Fault("device_lost", at=kill, after=2)])
    with FleetRuntime(n_workers=n_workers, devices=list(devices),
                      policy=BatchPolicy(max_batch=2, max_wait_s=1e9),
                      launch_retries=1, fault_plan=fp) as rt:
        for spec, _ in tenants:
            rt.open(spec)
        homes = dict(rt.stats()["placement"])
        on_kill = sorted(t for t, w in homes.items() if w == kill)
        check(bool(on_kill), f"no tenant placed on worker {kill}")
        streams = {spec.tenant_id: iter(chop(w, chunk_syms * spec.cfg.n_os,
                                             seed=i, jitter=0.5))
                   for i, (spec, w) in enumerate(tenants)}
        t0 = time.perf_counter()
        live = set(streams)
        while live:
            for tid in sorted(live):
                chunk = next(streams[tid], None)
                if chunk is None:
                    live.discard(tid)
                    rt.finish(tid)
                else:
                    rt.submit(tid, chunk)
        rt.drain()
        elapsed = time.perf_counter() - t0
        _check_streams(rt, tenants, "fleet")
        st = rt.stats()
    check(fp.fired == [("device_lost", kill)],
          f"fault plan fired {fp.fired}")
    check(st["migrations"] == 1 and st["recovery"]["device_losses"] == 1,
          f"expected one device loss and migration, got {st['recovery']}")
    check(st["recovery"]["sessions_poisoned"] == 0, "sessions poisoned")
    check(all(st["placement"][t] != kill for t in on_kill),
          f"streams left on the dead worker: {st['placement']}")
    for w in st["workers"]:
        landed = set(w["launch_devices"])
        check(bool(landed), f"worker {w['worker']} launched nothing")
        check(landed == {w["device"]},
              f"worker {w['worker']} ({w['device']}) launched on {landed}")
    launches = [sum(w["launch_devices"].values()) for w in st["workers"]]
    print(f"fleet: {n_workers} workers, launches per worker {launches}, "
          f"each on its own chip; worker {kill} lost, {len(on_kill)} streams "
          f"migrated, bitwise == offline and exactly once for all "
          f"{len(tenants)} tenants; smoke timing {elapsed:.3f} s incl. "
          f"compiles")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU found; this run does not fall back to "
              "the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {cache_dir}")

    phases = ([("engine", lambda: engine_phase(args.seed)),
               ("serve", lambda: serve_phase(args.seed))]
              if args.chips == 1 else
              [("halo", lambda: halo_phase(args.seed, devices[:4])),
               ("fleet", lambda: fleet_phase(args.seed, devices[:4]))])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"phase {name}: done in {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {"platform": d0.platform,
                                             "kind": d0.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
