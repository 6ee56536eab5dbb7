"""Stacked launches from an engine pool's resident weight table.

  * table launches ≡ each engine run alone, BITWISE, on the three fused
    datapaths: shuffled slot orders, one slot twice in a launch, a table
    with free slots;
  * engines that share no table (bare, or mixed with pooled ones) are
    stacked privately, with the same results;
  * a launch keeps the weights it was assembled with when, before it
    executes, an LRU pool smaller than the tenant count evicts one of its
    engines and rebuilds another into the freed slot; the served streams
    still equal offline;
  * an engine pool's table under threads racing builds, evictions, drops
    and launches' slot lookups: no slot is lost or shared, and every
    resident engine's slot holds its own rows;
  * the warm-up contract of the benchmark's open-loop driver: every
    (rows, width) shape warmed through `stacked_engine_fn` on the
    runtime's own pooled engines, in each worker's device scope, and then
    tenant sets never seen at warm-up serve without one backend compile
    (an `AsyncServeRuntime`, and a `FleetRuntime` on four virtual CPU
    devices).
"""
import json
import os
import pathlib
import random
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import equalizer as eq
from repro.core.engine import EqualizerEngine, WeightTable, stacked_engine_fn
from repro.kernels.cnn_eq.cnn_eq import table_rows
from repro.serve import BatchPolicy, EnginePool, ServeRuntime, TenantSpec, \
    chop

ROOT = pathlib.Path(__file__).resolve().parent.parent
CFG = eq.CNNEqConfig()
INT8_FMT = tuple((2, 5, 3, 4) for _ in range(CFG.layers))


def _weights(seed):
    params = eq.init(jax.random.PRNGKey(seed), CFG)
    return eq.folded_weights(eq.fold_bn(params, eq.init_bn_state(CFG), CFG))


def _engine(backend, seed):
    return EqualizerEngine(
        cfg=CFG, weights=_weights(seed), backend=backend, tile_m=32,
        formats=INT8_FMT if backend == "fused_int8" else None)


def _alone(engines, x):
    return np.concatenate([np.asarray(e(x[i:i + 1]))
                           for i, e in enumerate(engines)])


def _table_bitwise(backend):
    engines = [_engine(backend, seed) for seed in range(5)]
    table = WeightTable(capacity=8)                  # three slots stay free
    for i in (3, 0, 4, 1, 2):                        # slots ≠ engine order
        assert table.insert(engines[i])
    assert not table.insert(engines[0])              # already holds one
    rng = np.random.default_rng(0)
    for rows in (list(rng.permutation(5)), [2, 0, 2], [4, 1], [3]):
        launch = [engines[i] for i in rows]
        x = jnp.asarray(rng.standard_normal(
            (len(rows), 384 * CFG.n_os)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(stacked_engine_fn(launch)(x)), _alone(launch, x))
    # the bound snapshot reads the table's slots, not a private stack
    arrays, slots = table.rows(engines)
    assert sorted(slots.tolist()) == list(range(5))
    assert arrays[0][0].shape[0] == 8


def _bare_engines():
    pooled = [_engine("fused_bf16", seed) for seed in range(3)]
    table = WeightTable(capacity=4)
    for e in pooled:
        table.insert(e)
    bare = [_engine("fused_bf16", seed) for seed in range(3, 5)]
    rng = np.random.default_rng(1)
    stacked = []

    def stacking(rows):
        stacked.append(rows)
        return jax.named_scope("stacking")

    for launch in (bare, [pooled[1], bare[0], pooled[0]]):
        x = jnp.asarray(rng.standard_normal(
            (len(launch), 256 * CFG.n_os)).astype(np.float32))
        y = stacked_engine_fn(launch, stacking=stacking)(x)
        np.testing.assert_array_equal(np.asarray(y), _alone(launch, x))
    assert stacked == [2, 3]


def _spec(tid, seed):
    return TenantSpec(tid, CFG, weights=_weights(seed), backend="fused_bf16",
                      tile_m=32)


def _evict_between_assemble_and_execute():
    rt = ServeRuntime(BatchPolicy(max_batch=3, max_wait_s=1e9),
                      max_engines=2)
    specs = {tid: _spec(tid, seed) for seed, tid in enumerate("abc")}
    rng = np.random.default_rng(2)
    waves = {tid: rng.standard_normal(700 * CFG.n_os).astype(np.float32)
             for tid in specs}
    streams = {tid: chop(waves[tid], 500, seed=i, jitter=0.5)
               for i, tid in enumerate(specs)}
    rt.open(specs["a"])
    rt.open(specs["b"])
    engines = {tid: rt.sessions.get(tid).engine for tid in "ab"}
    table = engines["a"]._table
    slots = dict(zip("ab", table.rows([engines["a"], engines["b"]])[1]))
    for tid in "ab":
        rt.submit(tid, streams[tid].pop(0))
    (batch,) = rt.batcher.take_ready(force=True)     # table launch (a, b)
    assert rt.batcher.table_launches == 1
    rt.open(specs["c"])                              # evicts the LRU
    (gone,) = [tid for tid in "ab" if tid not in rt.pool]
    assert table.rows([engines[gone]]) is None
    # c's rows overwrote the evicted engine's slot before the launch ran
    assert table.rows([rt.sessions.get("c").engine])[1].tolist() == \
        [slots[gone]]
    rt.batcher.descatter(batch, rt.batcher.execute(batch))
    for tid in "abc":                                # a rebuilds on demand
        for chunk in streams[tid]:
            rt.submit(tid, chunk)
        rt.finish(tid)
    rt.drain()
    for tid, spec in specs.items():
        want = np.asarray(spec.build_engine()(jnp.asarray(waves[tid][None])))
        np.testing.assert_array_equal(rt.output(tid), want[0])


CASES = {
    "table-fused_bf16": lambda: _table_bitwise("fused_bf16"),
    "table-fused_int8": lambda: _table_bitwise("fused_int8"),
    "table-fused_fp32": lambda: _table_bitwise("fused_fp32"),
    "bare-engines": _bare_engines,
    "evict-between-assemble-and-execute": _evict_between_assemble_and_execute,
}


@pytest.mark.parametrize("case", list(CASES))
def test_table_launch(case):
    CASES[case]()


def test_pool_table_slots_survive_racing_threads():
    engines = {f"t{i}": _engine("fused_bf16", i) for i in range(12)}
    pool = EnginePool(max_engines=5)
    errors = []

    def worker(seed):
        rng = random.Random(seed)
        try:
            for _ in range(60):
                keys = rng.sample(sorted(engines), 3)
                got = [pool.get(k, lambda k=k: engines[k]) for k in keys]
                if rng.random() < 0.2:
                    pool.drop(keys[0])
                table = got[-1]._table
                held = table.rows(got) if table is not None else None
                if held is not None:       # a launch's slots: one each
                    assert len(set(held[1].tolist())) == len(set(keys))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    resident = [e for k, e in engines.items() if k in pool]
    (table,) = {e._table for e in resident}
    arrays, slots = table.rows(resident)
    assert len(set(slots.tolist())) == len(resident) <= 5
    assert len(table._free) == 5 - len(resident)
    for e, slot in zip(resident, slots):
        for (w, b), (tw, tb) in zip(table_rows(e._layer_weights(),
                                               e.backend), arrays):
            np.testing.assert_array_equal(np.asarray(tw[slot]),
                                          np.asarray(w))
            np.testing.assert_array_equal(np.asarray(tb[slot]),
                                          np.asarray(b))


WARM_SCRIPT = r"""
import contextlib
import json
import sys
import jax
import numpy as np
from bench import harness, program, weights

kind = sys.argv[1]
cfg = harness.load_json(harness.ROOT / "bench/configs/lp_proakis.json")
cfg["tile_m"] = 32
cfg["deployment"]["tenants_per_chip"] = 6
cfg["deployment"]["policy"] = {"max_batch": 3, "max_wait_s": 1e9}
devices = jax.devices() if kind == "fleet" else jax.devices()[:1]
topo, n_os = cfg["topology"], cfg["topology"]["n_os"]
n = 6 * len(devices)
ws = weights.tenant_weights(topo, 2 ** 31 + 5, n)
tids = [f"t{i}" for i in range(n)]
rt = program.runtime(cfg, devices, None)
for tid, w in zip(tids, ws):
    rt.open(program.tenant_spec(cfg, tid, w))
engine = program.engines_by_device(rt)[0][1][0]
quantum = engine.resolved_tile_m() * engine.total_stride
widths = [quantum * k for k in (1, 2, 3, 4)]   # carry + ≤ 2 quanta
for dev, engines in program.engines_by_device(rt):     # as open_loop warms
    for b in range(1, 4):
        fn = program.stacked_fn(engines[:b])
        scope = (jax.default_device(dev)
                 if dev is not None and jax.device_count() > 1
                 else contextlib.nullcontext())
        with scope:
            for w in widths:
                jax.block_until_ready(fn(jax.numpy.asarray(
                    np.zeros((b, w), np.float32))))
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
rng = np.random.default_rng(7)
# tenant sets never warmed: every tenant in reverse order, three rows a
# launch, twice over
for _ in range(2):
    for tid in reversed(tids):
        size = int(rng.integers(quantum // 2, 2 * quantum))
        rt.submit(tid, rng.standard_normal(size).astype(np.float32))
rt.drain()
bs = program.batchers(rt)
print(json.dumps({
    "compiles": compiles,
    "launches": sum(b.launches for b in bs),
    "table_launches": sum(b.table_launches for b in bs),
    "restacks": sum(b.restacks for b in bs),
    "multi_row": sum(1 for b in bs for rows in b.batch_sizes if rows > 1),
}))
rt.shutdown()
"""


@pytest.mark.parametrize("kind", ["async", "fleet"])
def test_warmed_runtime_serves_unseen_tenant_sets_without_compiling(
        kind, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", WARM_SCRIPT, kind],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["compiles"] == [], out
    assert out["restacks"] == 0, out
    assert out["table_launches"] == out["multi_row"] > 0, out
