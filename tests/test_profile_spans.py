"""The serving stack's host phases on the device profiler's timeline, the
micro-batcher's launch counters, and the named scopes and kernel names
that device profiles attribute op time by.

  * a synchronous `ServeRuntime` sequence with known memoized-fn hits,
    misses and weight re-stacks, and launched/useful samples and copied
    bytes derived from the assembled requests;
  * a short `AsyncServeRuntime` run under `jax.profiler.trace`: every
    documented `serve.*` span is in the profile, nested as documented,
    and one launch id joins assemble, execute, descatter and the chunks'
    `ChunkSpan`s;
  * a two-worker `FleetRuntime` under the profiler: each worker's launch
    spans carry its index as `worker` (a one-chip runtime's carry none);
  * the lowered `partitioned_apply` carries the scopes `partition`,
    `tile_windows`, `interleave` and `merge`, and the kernel its stable
    per-datapath name.
"""
import collections
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import equalizer as eq
from repro.core import stream_partition as sp
from repro.core.engine import EqualizerEngine
from repro.obs import Observability, annotate
from repro.serve import AsyncServeRuntime, BatchPolicy, FleetRuntime, \
    ServeRuntime, TenantSpec

CFG = eq.CNNEqConfig()
INT8_FMT = tuple((2, 5, 3, 4) for _ in range(CFG.layers))


def _spec(tid, seed, backend="fused_bf16"):
    params = eq.init(jax.random.PRNGKey(seed), CFG)
    folded = eq.fold_bn(params, eq.init_bn_state(CFG), CFG)
    return TenantSpec(tid, CFG, weights=eq.folded_weights(folded),
                      formats=INT8_FMT if backend == "fused_int8" else None,
                      backend=backend, tile_m=32)


def _chunk(rng, n=1024):
    return rng.standard_normal(n).astype(np.float32)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_annotate_outside_a_profiler_session_is_inert():
    with annotate("serve.submit", launch=3, rows=2):
        y = jnp.arange(4.0).sum()
    assert float(y) == 6.0


def test_sync_runtime_launch_counters():
    rt = ServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9),
                      clock=FakeClock())
    for i, tid in enumerate("abc"):
        rt.open(_spec(tid, i))
    widths = []                                  # plan widths per launch
    assemble = rt.batcher.assemble

    def spy(key, reqs):
        widths.append([r.plan.width for r in reqs])
        return assemble(key, reqs)

    rt.batcher.assemble = spy
    rng = np.random.default_rng(0)
    sizes = iter([1500, 2300, 1700, 2000, 1900, 1300, 2600])
    for tid in "abab":                 # (a, b): miss + restack, then hit
        rt.submit(tid, _chunk(rng, next(sizes)))
    rt.submit("c", _chunk(rng, next(sizes)))
    rt.drain()                         # (c): miss, one engine, no restack
    for tid in "ba":                   # (b, a): another order, a restack
        rt.submit(tid, _chunk(rng, next(sizes)))
    b = rt.batcher
    assert [len(w) for w in widths] == [2, 2, 1, 2], widths
    assert (b.group_fn_hits, b.group_fn_misses, b.restacks) == (1, 3, 2)
    e = rt.sessions.get("a").engine
    quantum = e.resolved_tile_m() * e.total_stride
    launched = sum(len(w) * -(-max(w) // quantum) * quantum for w in widths)
    assert b.useful_samples == sum(map(sum, widths))
    assert b.launched_samples == launched > b.useful_samples
    assert b.h2d_bytes == 4 * launched                 # float32 rows in
    assert b.d2h_bytes == 4 * launched // CFG.n_os     # a symbol per N_os
    snap = rt.obs.snapshot()["serve"]
    assert snap["restacks_total"] == 2
    assert snap["group_fn_hits_total"] == 1
    assert snap["useful_samples_total"] == b.useful_samples
    assert snap["launches_total"] == 4


# span -> the spans it may run inside, on the same thread (None: top level)
NESTING = {
    "serve.submit": None,
    "serve.pump": None,
    "serve.lock_wait": {"serve.submit", "serve.pump"},
    "serve.assemble": {"serve.submit", "serve.pump"},
    "serve.restack": {"serve.assemble"},
    "serve.queue_put": {"serve.submit", "serve.pump"},
    "serve.execute": None,
    "serve.h2d": {"serve.execute"},
    "serve.dispatch": {"serve.execute"},
    "serve.wait": {"serve.execute"},
    "serve.d2h": {"serve.execute"},
    "serve.descatter": None,
    "serve.engine_build": None,
}


def _profile_spans(log_dir):
    """Per host thread, the (name, start, end, stats) of its serve.* spans."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("serve.")]
            if evs:
                lines.append(evs)
    return lines


def _parent(span, line):
    """The innermost other serve.* span of the same thread around span."""
    name, s, e, _ = span
    best = None
    for other in line:
        if other is span or not (other[1] <= s and e <= other[2]):
            continue
        if best is None or other[2] - other[1] < best[2] - best[1]:
            best = other
    return best


def test_profiler_spans_nest_and_share_launch_ids(tmp_path):
    obs = Observability(tracing=True)
    rng = np.random.default_rng(1)
    with AsyncServeRuntime(BatchPolicy(max_batch=2, max_wait_s=0.02),
                           obs=obs) as rt:
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i in range(3):
                rt.open(_spec(f"t{i}", i))
            futs = [rt.submit(f"t{i}", _chunk(rng))
                    for _ in range(3) for i in range(3)]
            for f in futs:
                if f is not None:
                    f.result(timeout=300)
        finally:
            jax.profiler.stop_trace()
    lines = _profile_spans(tmp_path)
    seen = collections.Counter(sp[0] for line in lines for sp in line)
    assert set(seen) == set(NESTING), seen
    for line in lines:
        for span in line:
            parent = _parent(span, line)
            allowed = NESTING[span[0]]
            if allowed is None:
                assert parent is None, (span[0], parent)
            else:
                assert parent is not None and parent[0] in allowed, \
                    (span[0], parent)
    by_name = collections.defaultdict(list)
    for line in lines:
        for name, _, _, stats in line:
            by_name[name].append(stats)
    # a one-chip runtime's spans name no fleet worker
    assert not any("worker" in st for sts in by_name.values() for st in sts)
    rows = {st["launch"]: st["rows"] for st in by_name["serve.assemble"]}
    for phase in ("serve.execute", "serve.descatter"):
        ids = [st["launch"] for st in by_name[phase]]
        assert sorted(ids) == sorted(rows), phase
    assert all(st["rows"] >= 2 for st in by_name["serve.restack"])
    chunks = collections.Counter(s.launch for s in obs.tracer.sealed_spans())
    assert dict(chunks) == rows
    exported = [ev for ev in obs.chrome_trace()["traceEvents"]
                if ev["name"].startswith("chunk ")]
    assert {ev["args"]["launch"] for ev in exported} == set(rows)


def test_fleet_launch_spans_carry_the_worker_index(tmp_path):
    rng = np.random.default_rng(2)
    with FleetRuntime(n_workers=2, policy=BatchPolicy(max_batch=2,
                                                      max_wait_s=0.02),
                      obs=Observability(tracing=True)) as rt:
        for i in range(4):
            rt.open(_spec(f"t{i}", i))
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                for i in range(4):
                    rt.submit(f"t{i}", _chunk(rng))
            rt.drain()
        finally:
            jax.profiler.stop_trace()
        homes = rt.stats()["placement"]
    assert sorted(homes.values()) == [0, 0, 1, 1]
    worker = collections.defaultdict(set)     # (span, launch) -> workers
    launches = collections.defaultdict(set)   # (span, worker) -> launches
    for line in _profile_spans(tmp_path):
        for name, _, _, stats in line:
            if name in ("serve.assemble", "serve.execute",
                        "serve.descatter"):
                worker[name, stats["launch"]].add(stats.get("worker"))
                launches[name, stats.get("worker")].add(stats["launch"])
            elif name == "serve.restack":
                assert stats["worker"] in (0, 1), stats
    for phase in ("serve.assemble", "serve.execute", "serve.descatter"):
        assert {w for (n, w) in launches if n == phase} == {0, 1}, phase
    # one launch, one worker, in every phase
    assert all(len(ws) == 1 for ws in worker.values()), worker
    for w in (0, 1):
        assert (launches["serve.assemble", w]
                == launches["serve.execute", w]
                == launches["serve.descatter", w])


def _lowered(backend):
    params = eq.init(jax.random.PRNGKey(3), CFG)
    eng = EqualizerEngine.from_folded(
        eq.fold_bn(params, eq.init_bn_state(CFG), CFG), CFG,
        backend=backend,
        formats=INT8_FMT if backend == "fused_int8" else None,
        tile_m=16, interpret=False)
    x = jax.ShapeDtypeStruct((4 * 2048 * CFG.n_os,), jnp.float32)
    fn = jax.jit(lambda v: sp.partitioned_apply(eng, v, 4, CFG))
    return fn.trace(x).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)


@pytest.mark.parametrize("backend,name", [
    ("fused_fp32", "cnn_eq_fused_fp32"),
    ("fused_bf16", "cnn_eq_fused_bf16"),
    ("fused_int8", "cnn_eq_fused_int8"),
])
def test_lowered_partitioned_apply_carries_scopes_and_kernel_name(
        backend, name):
    text = _lowered(backend)
    op_names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("partition", "tile_windows", "interleave", "merge"):
        assert any(f"{scope}/" in n for n in op_names), scope
    # the split is static slices: no op under `partition/` is a gather
    assert not [n for n in op_names
                if re.search(r"partition/.*gather", n)], op_names
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [name]
