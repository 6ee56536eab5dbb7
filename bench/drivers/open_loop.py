"""Open-loop serving: many tenants stream chunks at a rate fixed in the
traffic file, whether or not earlier chunks have finished.

Tenants: the configuration's `tenants_per_chip` × the cell's chips, each
with its own weights and its own seeded waveform, replayed cyclically as
one unbounded stream. One chip runs `AsyncServeRuntime`; more run a
`FleetRuntime` with one worker per chip.

Arrivals: Poisson per tenant at `rate_syms_per_s` / tenants / chunk_syms
chunks a second; chunk sizes `chunk_syms` × N_os samples ± `jitter`. The
arrival times and the chunk sizes are drawn from `pattern_seed`, so every
run seed offers the same work; the run seed permutes which tenant gets
which arrival stream and the order of the sizes, and draws the waveforms
and the weights.

Timing: one generator thread submits each chunk at its due time (it runs
late when `submit` blocks or the host is busy; how late is recorded). A
chunk's latency runs from its due time to the resolution of its future,
which lands every symbol the chunk made emittable. A chunk whose future
failed, or that is not done a grace period after the window, is missing.

Correctness: once every chunk is done, a sample of tenants drawn from the
seed (and the tenant that sent the most) is compared, symbol for symbol,
with the plain reference over that tenant's whole submitted stream.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
from typing import Dict, List

import jax
import numpy as np

from .. import channels, program, weights
from ..reference import CONTROL, Reference, max_gap, receptive_halo, \
    total_stride
from ..stats import MISSING, latencies_from_due


class Schedule:
    """Arrival times (s from the window's start), tenant index and chunk
    size in samples of every chunk, in time order."""

    def __init__(self, traffic: Dict, rate: float, n_tenants: int,
                 seconds: float, seed: int, n_os: int):
        pat = np.random.default_rng(int(traffic["pattern_seed"]))
        per = rate / float(traffic["chunk_syms"]) / n_tenants
        times, slots = [], []
        for j in range(n_tenants):
            t = np.cumsum(pat.exponential(1.0 / per,
                                          int(per * seconds * 1.3) + 16))
            while t[-1] < seconds:
                t = np.concatenate([t, t[-1] + np.cumsum(
                    pat.exponential(1.0 / per, int(per * seconds) + 16))])
            t = t[t < seconds]
            times.append(t)
            slots.append(np.full(t.shape, j))
        t = np.concatenate(times)
        jit = float(traffic["jitter"])
        sizes = np.rint(traffic["chunk_syms"] * n_os * pat.uniform(
            1.0 - jit, 1.0 + jit, t.shape[0])).astype(np.int64)
        run = np.random.default_rng(seed)
        perm = run.permutation(n_tenants)
        sizes = sizes[run.permutation(sizes.shape[0])]
        order = np.argsort(t, kind="stable")
        self.t = t[order]
        self.tenant = perm[np.concatenate(slots)[order]]
        self.size = np.maximum(1, sizes)


class Served:
    """What one measured window produced."""

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.lag = np.zeros(n)
        self.done: List = [None] * n
        self.n_syms = np.zeros(n, np.int64)
        self.failed = np.zeros(n, bool)
        self._left = n
        self._lock = threading.Lock()
        self.all_done = threading.Event()
        if n == 0:
            self.all_done.set()

    def land(self, k: int, fut=None) -> None:
        """Record chunk k's completion (fut None: nothing was due)."""
        t = time.perf_counter()
        if fut is not None and (fut.cancelled()
                                or fut.exception() is not None):
            self.failed[k] = True
        else:
            self.n_syms[k] = 0 if fut is None else len(fut.result())
            self.done[k] = t
        with self._lock:
            self._left -= 1
            if self._left == 0:
                self.all_done.set()


class State:
    """A set-up runtime with its tenants, streams and warm programs."""

    def __init__(self, ctx):
        cfg, traffic = ctx.cell.config, ctx.cell.traffic
        self.ctx, self.cfg, self.traffic = ctx, cfg, traffic
        self.topo = cfg["topology"]
        dep = cfg["deployment"]
        per_chip = int(ctx.sizes.get("tenants_per_chip",
                                     dep["tenants_per_chip"]))
        self.n = per_chip * len(ctx.devices)
        base_syms = int(ctx.sizes.get("base_syms", traffic["base_syms"]))
        self.n_os = int(self.topo["n_os"])
        self.waves = channels.waveforms(cfg["channel"], ctx.seed, self.n,
                                        base_syms)
        self.base = self.waves.shape[1]
        self.w = weights.tenant_weights(self.topo, ctx.seed, self.n)
        self.obs = program.observability(ctx.trace, 1 << 20)
        run_cfg = dict(cfg, deployment=dict(dep, tenants_per_chip=per_chip))
        self.rt = program.runtime(run_cfg, ctx.devices, self.obs)
        self.tids = [f"t{i:04d}" for i in range(self.n)]
        self.pos = np.zeros(self.n, np.int64)   # samples submitted
        for tid, w in zip(self.tids, self.w):
            self.rt.open(program.tenant_spec(cfg, tid, w))
        self.warm()

    def widths(self) -> List[int]:
        """Launch widths the traffic can produce: carry plus the largest
        chunk, in whole tile buckets (tile_m positions of T samples)."""
        ts, halo = total_stride(self.topo), receptive_halo(self.topo)
        tile = int(self.cfg["tile_m"])
        ctx_pos = -(-halo // ts)
        carry = (tile + ctx_pos + ctx_pos + 2) * ts
        big = math.ceil(self.traffic["chunk_syms"] * self.n_os
                        * (1.0 + float(self.traffic["jitter"])))
        q = tile * ts
        return list(range(q, -(-(carry + big) // q) * q + q, q))

    def warm(self) -> None:
        """Compile and run every (batch, width) launch shape on every
        device that serves tenants, in the contexts the runtime uses: the
        weights are stacked outside any device scope (assembly), the
        launch runs in its worker's device scope (fleet workers)."""
        max_batch = int(self.cfg["deployment"]["policy"]["max_batch"])
        for dev, engines in program.engines_by_device(self.rt):
            for b in range(1, min(max_batch, len(engines)) + 1):
                fn = program.stacked_fn(engines[:b])
                scope = (jax.default_device(dev)
                         if dev is not None and jax.device_count() > 1
                         else contextlib.nullcontext())
                with scope:
                    for w in self.widths():
                        for _ in range(2):
                            jax.block_until_ready(fn(jax.numpy.asarray(
                                np.zeros((b, w), np.float32))))

    def chunk(self, i: int, size: int) -> np.ndarray:
        p = int(self.pos[i] % self.base)
        self.pos[i] += size
        wave = self.waves[i]
        if p + size <= self.base:
            return wave[p:p + size]
        return np.concatenate([wave[p:], wave[:p + size - self.base]])

    def counters(self) -> Dict[str, int]:
        bs = program.batchers(self.rt)
        return {"requests": sum(b.total_requests for b in bs),
                "launches": sum(b.launches for b in bs)}

    def measure(self, sched: Schedule, grace_s: float) -> Served:
        """Offer the schedule's chunks at their due times; wait for all."""
        out = Served(len(sched.t))
        rt, tids = self.rt, self.tids
        t0 = time.perf_counter()
        out.due[:] = t0 + sched.t
        for k in range(len(sched.t)):
            d = out.due[k]
            now = time.perf_counter()
            if d > now:
                time.sleep(d - now)
                now = time.perf_counter()
            out.lag[k] = now - d
            i = int(sched.tenant[k])
            fut = rt.submit(tids[i], self.chunk(i, int(sched.size[k])))
            if fut is None:               # buffered: nothing emittable yet
                out.land(k)
            else:
                fut.add_done_callback(functools.partial(out.land, k))
        self.t_close = time.perf_counter()
        out.all_done.wait(grace_s)
        self.t_end = time.perf_counter()
        return out

    def compare(self, idx: List[int], control: bool) -> Dict[str, float]:
        """Widest gap and missing symbols over the tenants `idx`."""
        ts, halo = total_stride(self.topo), receptive_halo(self.topo)
        vp = int(self.topo["v_parallel"])
        ref = Reference(self.topo, self.cfg["datapath"], self.cfg.get(
            "formats"))
        low = Reference(self.topo, CONTROL[self.cfg["datapath"]],
                        self.cfg.get("formats")) if control else None
        gap, missing = 0.0, 0
        for i in idx:
            n = int(self.pos[i])
            stream = np.resize(self.waves[i], n)
            due = 0 if n <= halo else min((n - 1 - halo) // ts + 1, n // ts)
            try:
                got = self.rt.output(self.tids[i])
            except Exception:  # noqa: BLE001 — a poisoned stream
                got = np.zeros((0,), np.float32)
            want = ref(stream, self.w[i])[:got.shape[0]]
            if low is not None:
                got = low(stream, self.w[i])[:got.shape[0]]
            gap = max(gap, max_gap(got, want))
            missing += max(0, due * vp - got.shape[0])
        return {"max_abs_gap": gap, "missing_symbols": missing}

    def close(self) -> None:
        self.rt.shutdown()


def _span_labels(spans) -> List:
    """(label, start, end) of every launch phase, for naming idle gaps."""
    out = []
    for s in spans:
        m = s.marks
        if "submit" in m and "assemble" in m:
            out.append(("queued (submit to assemble)", m["submit"],
                        m["assemble"]))
        if "assemble" in m and "launch" in m:
            out.append(("host: assemble to launch", m["assemble"],
                        m["launch"]))
        if "launch" in m and "execute" in m:
            out.append(("launch (dispatch, device, copy back)",
                        m["launch"], m["execute"]))
        if "execute" in m and "descatter" in m:
            out.append(("host: descatter", m["execute"], m["descatter"]))
    return out


def _launch_phases(spans) -> Dict[str, List[float]]:
    """Per launch: host phases (launch − assemble) + (descatter −
    execute); per chunk: queue wait (launch − submit)."""
    per_launch = {}
    waits = []
    for s in spans:
        m = s.marks
        if all(p in m for p in ("submit", "assemble", "launch", "execute",
                                "descatter")):
            per_launch[(m["launch"], m["execute"])] = (
                (m["launch"] - m["assemble"])
                + (m["descatter"] - m["execute"]))
            waits.append(m["launch"] - m["submit"])
    return {"host_phase_s": list(per_launch.values()), "wait_s": waits}


def run(ctx) -> Dict:
    st = State(ctx)
    try:
        traffic = st.traffic
        sched = Schedule(traffic, float(traffic["rate_syms_per_s"]), st.n,
                         ctx.seconds, ctx.seed, st.n_os)
        before = st.counters()
        with ctx.window() as win:
            t_start = time.perf_counter()
            served = st.measure(sched, float(traffic["grace_s"]))
        after = st.counters()
        spans = program.spans(st.obs) if ctx.trace else []
        win.reduce(_span_labels(spans))
        device = ctx.device_info()
        rng = np.random.default_rng(ctx.seed)
        n_check = min(st.n, int(traffic["check_tenants"]))
        idx = set(int(i) for i in rng.choice(st.n, n_check, replace=False))
        idx.add(int(np.argmax(st.pos)))
        checks = st.compare(sorted(idx), ctx.control)
        lat = latencies_from_due(
            served.due, [None if f else d
                         for d, f in zip(served.done, served.failed)])
        phases = _launch_phases(spans)
        n_missing = sum(1 for v in lat if v == MISSING)
        rec = {
            "setup_s": t_start - ctx.t0,
            "attempted": len(lat),
            "failed": n_missing,
            "latency_s": lat,
            "missing_s": st.t_end - float(np.min(served.due)),
            "gen_lag_s": served.lag.tolist(),
            "window_s": st.t_end - t_start if n_missing else
            max(d for d in served.done if d is not None) - t_start,
            "symbols": int(served.n_syms.sum()),
            "launch_rows": after["requests"] - before["requests"],
            "launches": after["launches"] - before["launches"],
            "host_phase_s": phases["host_phase_s"],
            "wait_s": phases["wait_s"],
            "topology": st.topo,
            "backend": st.cfg["backend"],
            "device": device,
            "trace": win.reduced,
            "breakdown": win.breakdown,
            "notes": [f"offered {sched.size.sum() // st.n_os} symbols in "
                      f"{len(lat)} chunks over {ctx.seconds} s; generator "
                      f"lag p99 {np.percentile(served.lag, 99) * 1e3:.3f} "
                      f"ms, max {served.lag.max() * 1e3:.3f} ms; window "
                      f"closed {st.t_close - t_start:.3f} s"],
            "checks": [ctx.check(k, v) for k, v in checks.items()],
        }
        return rec
    finally:
        st.close()
