"""Closed-loop bulk equalization: the paper's HT deployment unit, steps
back to back.

Each step takes the next of `segments` seeded waveforms held on the host
(N_i instances × ℓ_inst symbols each), copies it to the chip, runs the
partitioned equalizer (split with overlap, fused kernel, merge) and brings
the symbols back to the host. The window counts the symbols of every step
that ended in it, over the time from its first step's start to its last
step's end.

Correctness: a sample of the window's steps, drawn from the seed by
reservoir sampling, is compared after the window with the plain reference
over the unsplit segment (float64 numpy on the host).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import channels, program, weights
from ..harness import BenchError
from ..reference import CONTROL, Reference, max_gap


def run(ctx):
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    topo, dep = cfg["topology"], cfg["deployment"]
    n_inst = int(ctx.sizes.get("n_instances", dep["n_instances"]))
    l_inst = int(ctx.sizes.get("l_inst", dep["l_inst"]))
    n_seg = int(traffic["segments"])
    syms_per_step = n_inst * l_inst

    waves = channels.waveforms(cfg["channel"], ctx.seed, n_seg,
                               syms_per_step)
    (w,) = weights.tenant_weights(topo, ctx.seed, 1)
    eng = program.engine(cfg, w)
    dcfg = dict(cfg, deployment=dict(dep, n_instances=n_inst))
    step = program.partitioned_step(dcfg, eng)
    for seg in waves:                             # compile and warm
        y = np.asarray(step(jax.device_put(seg)))
        if y.shape != (syms_per_step,):
            raise BenchError(f"step returned {y.shape}")

    rng = np.random.default_rng(ctx.seed)
    keep = int(traffic["check_steps"])
    kept = []                                     # reservoir of (seg, y)
    ann = jax.profiler.TraceAnnotation if ctx.trace else None
    n = 0
    with ctx.window() as win:
        t_start = time.perf_counter()
        while True:
            i = n % n_seg
            if ann is None:
                y = np.asarray(step(jax.device_put(waves[i])))
            else:
                with ann("h2d"):
                    x = jax.device_put(waves[i])
                with ann("step"):
                    y = step(x)
                    y.block_until_ready()
                with ann("d2h"):
                    y = np.asarray(y)
            n += 1
            if len(kept) < keep:
                kept.append((i, y))
            else:
                j = int(rng.integers(0, n))
                if j < keep:
                    kept[j] = (i, y)
            if time.perf_counter() - t_start >= ctx.seconds:
                break
        t_end = time.perf_counter()
    win.reduce()

    device = ctx.device_info()
    ref = Reference(topo, cfg["datapath"], cfg.get("formats"))
    want = {i: ref(waves[i], w) for i in sorted({i for i, _ in kept})}
    if ctx.control:          # the reference one precision below, in place
        low = Reference(topo, CONTROL[cfg["datapath"]], cfg.get("formats"))
        kept = [(i, low(waves[i], w)) for i, _ in kept]
    gaps = [max_gap(y, want[i]) for i, y in kept]
    return {
        "setup_s": t_start - ctx.t0,
        "attempted": n,
        "failed": sum(1 for g in gaps if g > ctx.limit("max_abs_gap")),
        "window_s": t_end - t_start,
        "symbols": n * syms_per_step,
        "steps": n,
        "launch_rows": n * n_inst,
        "topology": topo,
        "backend": cfg["backend"],
        "device": device,
        "trace": win.reduced,
        "breakdown": win.breakdown,
        "checks": [ctx.check("max_abs_gap", max(gaps))],
    }
