"""Serving runtime (repro.serve) — the ISSUE-3/ISSUE-4 acceptance surface.

  * chunked-streaming equivalence: a property-style sweep over chunk sizes
    (including chunks smaller than the receptive field) asserting
    serve output == offline engine output per backend — BITWISE for the
    fused fp32/bf16/int8 datapaths; ≤2 ULP for "ref" (the pure-jnp oracle's
    op widths depend on stream length, so XLA may contract differently).
    The sweep runs under BOTH drivers: the synchronous `ServeRuntime` and
    the threaded `AsyncServeRuntime` (same chunker, same stacked launches —
    only the driving loop differs);
  * engine-pool LRU eviction (rebuild-after-evict keeps streams correct);
  * micro-batching policy: max_batch and max_wait triggers, grouping by
    engine group_key, latency accounting;
  * chunker unit behaviour (carry bound, tile alignment, end-of-stream);
  * traffic stats (batch-occupancy / launch-width histograms) and the
    serve-aware autotune re-tune they feed;
  * async runtime: per-chunk futures, timer-driven max_wait flush,
    launch-failure retry (transient) and session poisoning (terminal),
    multi-tenant stress with random chunk sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, equalizer as eq
from repro.core.engine import BACKENDS, EqualizerEngine
from repro.serve import (AsyncServeRuntime, BatchPolicy, EnginePool,
                         MicroBatcher, ServeRuntime, StreamChunker,
                         TenantSpec, TrafficStats, chop)

CFG = eq.CNNEqConfig()
INT8_FMT = tuple((2, 5, 3, 4) for _ in range(CFG.layers))
KEY = jax.random.PRNGKey(0)
ULP_TOL = 5e-6


def _spec(tid, backend, seed, cfg=CFG, tile_m=32):
    params = eq.init(jax.random.PRNGKey(seed), cfg)
    folded = eq.fold_bn(params, eq.init_bn_state(cfg), cfg)
    return TenantSpec(
        tid, cfg, weights=eq.folded_weights(folded),
        formats=INT8_FMT if backend == "fused_int8" else None,
        backend=backend, tile_m=tile_m)


def _offline(spec, wave):
    return np.asarray(spec.build_engine()(jnp.asarray(wave[None])))[0]


def _replay_round_robin(rt, streams):
    ids = list(streams)
    iters = {t: iter(streams[t]) for t in ids}
    live = set(ids)
    while live:
        for t in list(live):
            c = next(iters[t], None)
            if c is None:
                live.discard(t)
                rt.finish(t)
            else:
                rt.submit(t, c)
    rt.drain()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# chunked-streaming equivalence sweep (both drivers)
# ---------------------------------------------------------------------------

def _make_runtime(driver, policy, **kw):
    """Build either driver; async runtimes must be shut down by the caller."""
    if driver == "async":
        return AsyncServeRuntime(policy, **kw)
    return ServeRuntime(policy, **kw)


@pytest.mark.parametrize("driver", ["sync", "async"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("chunk_samples", [
    17,       # smaller than the receptive field (halo = 68 samples)
    160,      # a few positions per chunk, not stride-aligned
    10_000,   # whole stream in one chunk
])
def test_chunked_serve_equals_offline(driver, backend, chunk_samples):
    if driver == "async" and chunk_samples == 17:
        pytest.skip("sub-receptive-field arrival already covered by the "
                    "sync sweep and the async stress test (compile cost)")
    n_tenants, n_syms = 2, 523                       # odd on purpose
    rt = _make_runtime(driver,
                       BatchPolicy(max_batch=n_tenants, max_wait_s=1e9))
    try:
        specs = [_spec(f"t{i}", backend, seed=i) for i in range(n_tenants)]
        rng = np.random.default_rng(42)
        waves = [rng.standard_normal(n_syms * CFG.n_os).astype(np.float32)
                 for _ in range(n_tenants)]
        for s in specs:
            rt.open(s)
        streams = {s.tenant_id: chop(w, chunk_samples, seed=i, jitter=0.5)
                   for i, (s, w) in enumerate(zip(specs, waves))}
        _replay_round_robin(rt, streams)
        for s, w in zip(specs, waves):
            got = rt.output(s.tenant_id)
            want = _offline(s, w)
            assert got.shape == want.shape
            if backend == "ref":
                np.testing.assert_allclose(got, want, rtol=0, atol=ULP_TOL)
            else:
                # fused backends: BITWISE — the chunker keeps its carry
                # tile-aligned so every emitted position repeats the offline
                # tile computation exactly (int8 thereby also beats its
                # ≤1-LSB bound); holds under BOTH drivers (same launches)
                np.testing.assert_array_equal(got, want)
    finally:
        if driver == "async":
            rt.shutdown()


def test_chunked_serve_single_sample_trickle():
    """Degenerate arrival pattern: 1-sample chunks still reassemble the
    offline stream bitwise (fp32 fused)."""
    rt = ServeRuntime(BatchPolicy(max_batch=64, max_wait_s=1e9))
    spec = _spec("drip", "fused_fp32", seed=7)
    rt.open(spec)
    rng = np.random.default_rng(3)
    wave = rng.standard_normal(120 * CFG.n_os).astype(np.float32)
    for v in wave:
        rt.submit("drip", np.array([v], np.float32))
    got_stream = rt.close("drip")
    np.testing.assert_array_equal(got_stream, _offline(spec, wave))


def test_close_flushes_tail_and_matches_offline():
    rt = ServeRuntime(BatchPolicy(max_batch=4, max_wait_s=1e9))
    spec = _spec("solo", "fused_int8", seed=1)
    rt.open(spec)
    rng = np.random.default_rng(5)
    wave = rng.standard_normal(301 * CFG.n_os + 7).astype(np.float32)
    for c in chop(wave, 200, seed=1, jitter=0.3):
        rt.submit("solo", c)
    got = rt.close("solo")                 # finish + drain + release
    np.testing.assert_array_equal(got, _offline(spec, wave))
    assert "solo" not in rt.sessions


# ---------------------------------------------------------------------------
# engine pool / session manager
# ---------------------------------------------------------------------------

def test_engine_pool_lru_eviction():
    pool = EnginePool(max_engines=2)
    built = []

    def mk(name):
        def build():
            built.append(name)
            return f"engine-{name}"
        return build

    assert pool.get("a", mk("a")) == "engine-a"
    assert pool.get("b", mk("b")) == "engine-b"
    assert pool.get("a", mk("a")) == "engine-a"      # hit refreshes a
    assert pool.get("c", mk("c")) == "engine-c"      # evicts b (LRU)
    assert "b" not in pool and "a" in pool and "c" in pool
    assert pool.get("b", mk("b")) == "engine-b"      # rebuild, evicts a
    assert "a" not in pool
    assert built == ["a", "b", "c", "b"]
    st = pool.stats()
    assert st["evictions"] == 2 and st["hits"] == 1 and st["misses"] == 4
    assert len(pool) == 2


def test_streams_survive_engine_eviction():
    """More tenants than pool slots: engines are rebuilt on demand and the
    streams stay bitwise-correct (chunker state is session-owned)."""
    n_tenants = 4
    rt = ServeRuntime(BatchPolicy(max_batch=n_tenants, max_wait_s=1e9),
                      max_engines=2)                 # < n_tenants slots
    specs = [_spec(f"s{i}", "fused_fp32", seed=10 + i)
             for i in range(n_tenants)]
    rng = np.random.default_rng(11)
    waves = [rng.standard_normal(257 * CFG.n_os).astype(np.float32)
             for _ in range(n_tenants)]
    for s in specs:
        rt.open(s)
    streams = {s.tenant_id: chop(w, 300, seed=i)
               for i, (s, w) in enumerate(zip(specs, waves))}
    _replay_round_robin(rt, streams)
    assert rt.pool.stats()["evictions"] > 0          # pressure really hit
    for s, w in zip(specs, waves):
        np.testing.assert_array_equal(rt.output(s.tenant_id),
                                      _offline(s, w))


# ---------------------------------------------------------------------------
# micro-batching policy
# ---------------------------------------------------------------------------

def test_max_batch_triggers_immediate_coalesced_launch():
    clock = FakeClock()
    rt = ServeRuntime(BatchPolicy(max_batch=3, max_wait_s=1e9), clock=clock)
    specs = [_spec(f"m{i}", "fused_fp32", seed=20 + i) for i in range(3)]
    rng = np.random.default_rng(13)
    waves = [rng.standard_normal(128 * CFG.n_os).astype(np.float32)
             for _ in range(3)]
    for s in specs:
        rt.open(s)
    rt.submit("m0", waves[0])
    rt.submit("m1", waves[1])
    assert rt.batcher.launches == 0                  # below max_batch, no t
    rt.submit("m2", waves[2])                        # 3rd pending → launch
    assert rt.batcher.launches == 1
    assert list(rt.batcher.batch_sizes) == [3]       # ONE stacked call
    st = rt.stats()
    assert st["requests"] == 3 and st["mean_batch"] == 3.0
    assert st["p99_latency_ms"] >= 0.0


def test_max_wait_triggers_time_flush():
    clock = FakeClock()
    rt = ServeRuntime(BatchPolicy(max_batch=100, max_wait_s=0.5),
                      clock=clock)
    spec = _spec("w0", "fused_fp32", seed=31)
    rt.open(spec)
    rng = np.random.default_rng(17)
    wave = rng.standard_normal(128 * CFG.n_os).astype(np.float32)
    rt.submit("w0", wave)
    assert rt.batcher.launches == 0
    clock.advance(0.1)
    assert rt.pump() == 0                            # not old enough yet
    clock.advance(0.6)                               # oldest now > max_wait
    assert rt.pump() == 1
    assert rt.batcher.launches == 1
    np.testing.assert_array_equal(
        rt.output("w0"), _offline(spec, wave)[:len(rt.output("w0"))])


def test_close_does_not_drain_other_tenants():
    """Closing one tenant launches only ITS pending requests; another
    tenant's partial batch keeps waiting for its max_batch/max_wait."""
    clock = FakeClock()
    rt = ServeRuntime(BatchPolicy(max_batch=8, max_wait_s=1e9), clock=clock)
    a = _spec("closer", "fused_fp32", seed=60)
    b = _spec("waiter", "fused_fp32", seed=61)
    rng = np.random.default_rng(37)
    # ≥ one tile of positions (tile_m=32 → 512 syms) so the offline call
    # tiles exactly like serve (see chunker docstring boundary note)
    wa = rng.standard_normal(600 * CFG.n_os).astype(np.float32)
    wb = rng.standard_normal(600 * CFG.n_os).astype(np.float32)
    rt.open(a)
    rt.open(b)
    rt.submit("closer", wa)
    rt.submit("waiter", wb)
    got = rt.close("closer")                         # flushes only "closer"
    np.testing.assert_array_equal(got, _offline(a, wa))
    assert rt.batcher.pending() == 1                 # waiter still queued
    assert all(s <= 2 for s in rt.batcher.batch_sizes)
    rt.drain()
    assert rt.batcher.pending() == 0


def test_groups_split_by_backend():
    """Tenants on different backends never share a stacked launch."""
    clock = FakeClock()
    rt = ServeRuntime(BatchPolicy(max_batch=4, max_wait_s=1e9), clock=clock)
    specs = ([_spec(f"g32-{i}", "fused_fp32", seed=40 + i) for i in range(2)]
             + [_spec(f"g8-{i}", "fused_int8", seed=50 + i)
                for i in range(2)])
    rng = np.random.default_rng(23)
    for s in specs:
        rt.open(s)
        rt.submit(s.tenant_id,
                  rng.standard_normal(200 * CFG.n_os).astype(np.float32))
    assert rt.batcher.launches == 0
    rt.drain()
    assert sorted(rt.batcher.batch_sizes) == [2, 2]  # one per group


# ---------------------------------------------------------------------------
# chunker unit behaviour
# ---------------------------------------------------------------------------

def test_chunker_carry_is_bounded_and_tile_aligned():
    ch = StreamChunker(halo=68, total_stride=16, tile_m=8)
    rng = np.random.default_rng(29)
    for _ in range(50):
        ch.push(rng.standard_normal(130).astype(np.float32))
        plan = ch.plan()
        if plan is not None:
            ch.commit(plan)
            assert ch._o_pos % ch.tile_m == 0        # tile-aligned carry
    # carry never exceeds context + one tile + one pending stride round
    assert ch.carry_samples <= (ch._ctx_pos + ch.tile_m + 1) * ch.ts + 130


def test_chunker_rejects_push_after_finish():
    ch = StreamChunker(halo=4, total_stride=2, tile_m=4)
    ch.push(np.zeros(8, np.float32))
    ch.finish()
    with pytest.raises(RuntimeError, match="finished"):
        ch.push(np.zeros(2, np.float32))


def test_chunker_emits_exact_offline_position_count():
    ch = StreamChunker(halo=68, total_stride=16, tile_m=16)
    total = 0
    rng = np.random.default_rng(31)
    for n in (7, 100, 33, 501, 16, 3):
        ch.push(rng.standard_normal(n).astype(np.float32))
        total += n
    ch.finish()
    emitted = 0
    while True:
        p = ch.plan()
        if p is None:
            break
        ch.commit(p)
        emitted += p.n_emit
    assert emitted == total // 16                    # ⌊W/ts⌋, like offline


# ---------------------------------------------------------------------------
# traffic stats (serve-aware autotune inputs)
# ---------------------------------------------------------------------------

def test_traffic_stats_histograms():
    st = TrafficStats()
    assert st.mode_occupancy() == 0 and st.median_width() == 0
    for b, w in [(2, 512), (2, 512), (3, 1024), (2, 256), (1, 512)]:
        st.record(b, w)
    assert st.launches == 5
    assert st.occupancy == {2: 3, 3: 1, 1: 1}
    assert st.widths == {512: 3, 1024: 1, 256: 1}
    assert st.mode_occupancy() == 2
    assert st.median_width() == 512
    d = st.as_dict()
    assert d["launches"] == 5 and d["mode_occupancy"] == 2
    assert d["widths"] == {256: 1, 512: 3, 1024: 1}


def test_traffic_stats_mode_tie_is_deterministic():
    st = TrafficStats()
    st.record(4, 512)
    st.record(2, 512)
    # tie between 2 and 4 → smallest wins (sorted iteration), every time
    assert st.mode_occupancy() == 2


def test_micro_batcher_records_traffic_per_tune_key():
    rt = ServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9))
    specs = ([_spec(f"f{i}", "fused_fp32", seed=70 + i) for i in range(2)]
             + [_spec(f"q{i}", "fused_int8", seed=72 + i) for i in range(2)])
    rng = np.random.default_rng(41)
    for s in specs:
        rt.open(s)
        rt.submit(s.tenant_id,
                  rng.standard_normal(200 * CFG.n_os).astype(np.float32))
    rt.drain()
    assert len(rt.batcher.traffic) == 2              # one per (cfg, backend)
    for st in rt.batcher.traffic.values():
        assert st.launches >= 1
        assert st.mode_occupancy() == 2              # both groups coalesced
        assert st.median_width() > 0
    # width histogram support is quantized: every width is a whole number
    # of tile quanta (tile_m=32 · total_stride)
    ts = specs[0].build_engine().total_stride
    for st in rt.batcher.traffic.values():
        assert all(w % (32 * ts) == 0 for w in st.widths)


# ---------------------------------------------------------------------------
# serve-aware autotune
# ---------------------------------------------------------------------------

def test_serve_aware_retune_on_warm_histogram(tmp_path, monkeypatch):
    """After the histogram warms up, a tile_m='auto' tenant gets a tile
    tuned at the OBSERVED (occupancy, width) shape; the tile is frozen into
    the session's spec copy (caller's spec untouched) and the stream stays
    bitwise-equal to the frozen spec's offline engine."""
    monkeypatch.setattr(autotune, "CACHE_PATH",
                        tmp_path / "autotune_serve.json")
    monkeypatch.setattr(autotune, "DEFAULT_TILES", (8, 16))
    rt = ServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9,
                                  retune_after=3))
    warm = [_spec(f"warm{i}", "fused_fp32", seed=80 + i, tile_m=16)
            for i in range(2)]
    rng = np.random.default_rng(43)
    for s in warm:
        rt.open(s)
    for _ in range(4):                               # 4 coalesced launches
        for s in warm:
            rt.submit(s.tenant_id,
                      rng.standard_normal(128 * CFG.n_os).astype(np.float32))
    rt.drain()
    assert next(iter(rt.batcher.traffic.values())).launches >= 3

    auto_spec = _spec("tuned", "fused_fp32", seed=90, tile_m="auto")
    sess = rt.open(auto_spec)
    assert isinstance(sess.spec.tile_m, int)         # serve-aware tile froze
    assert sess.spec.tile_m in (8, 16)
    assert auto_spec.tile_m == "auto"                # caller's spec untouched
    assert sess.chunker.tile_m == sess.spec.tile_m   # alignment matches

    wave = rng.standard_normal(300 * CFG.n_os).astype(np.float32)
    for c in chop(wave, 250, seed=4):
        rt.submit("tuned", c)
    got = rt.close("tuned")
    # parity is against the session's FROZEN spec (its tile), per contract
    np.testing.assert_array_equal(got, _offline(sess.spec, wave))


def test_serve_aware_retune_cold_histogram_and_explicit_tile(monkeypatch):
    """Before warm-up the tuner returns None (single-stream autotune path);
    explicit integer tiles are never re-tuned."""
    from repro.serve.runtime import _serve_tile
    rt = ServeRuntime(BatchPolicy(retune_after=3))
    eng = _spec("probe", "fused_fp32", seed=95, tile_m=16).build_engine()
    assert _serve_tile(rt.batcher, eng) is None      # no traffic at all
    # retune disabled entirely
    rt0 = ServeRuntime(BatchPolicy(retune_after=0))
    assert _serve_tile(rt0.batcher, eng) is None
    # explicit tile spec: tuner is bypassed at the Session level
    sess = rt.open(_spec("explicit", "fused_fp32", seed=96, tile_m=32))
    assert sess.spec.tile_m == 32


# ---------------------------------------------------------------------------
# async runtime
# ---------------------------------------------------------------------------

def test_async_per_chunk_futures_bitwise():
    """Every submit()/finish() future resolves to exactly the symbols that
    chunk emitted; their concatenation is the offline stream, bitwise."""
    with AsyncServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9)) as rt:
        specs = [_spec(f"fut{i}", "fused_fp32", seed=100 + i)
                 for i in range(2)]
        rng = np.random.default_rng(47)
        waves = [rng.standard_normal(523 * CFG.n_os).astype(np.float32)
                 for _ in range(2)]
        for s in specs:
            rt.open(s)
        futs = {s.tenant_id: [] for s in specs}
        streams = {s.tenant_id: chop(w, 300, seed=i, jitter=0.4)
                   for i, (s, w) in enumerate(zip(specs, waves))}
        iters = {t: iter(c) for t, c in streams.items()}
        live = set(iters)
        while live:
            for t in list(live):
                c = next(iters[t], None)
                f = rt.submit(t, c) if c is not None else rt.finish(t)
                if c is None:
                    live.discard(t)
                if f is not None:
                    futs[t].append(f)
        rt.drain()
        for s, w in zip(specs, waves):
            want = _offline(s, w)
            parts = [f.result(timeout=10) for f in futs[s.tenant_id]]
            np.testing.assert_array_equal(np.concatenate(parts), want)
            np.testing.assert_array_equal(rt.output(s.tenant_id), want)


def test_async_timer_flushes_max_wait_without_caller_pump():
    """The timer thread honours max_wait_s on its own — a single pending
    chunk below max_batch launches with NO pump()/drain() call."""
    with AsyncServeRuntime(BatchPolicy(max_batch=64, max_wait_s=0.05)) as rt:
        spec = _spec("timer", "fused_fp32", seed=110)
        rt.open(spec)
        rng = np.random.default_rng(53)
        wave = rng.standard_normal(128 * CFG.n_os).astype(np.float32)
        fut = rt.submit("timer", wave)
        assert fut is not None
        syms = fut.result(timeout=30)                # resolved by the timer
        np.testing.assert_array_equal(
            syms, _offline(spec, wave)[:syms.shape[0]])


def test_async_stress_random_chunks_with_transient_launch_failures(
        monkeypatch):
    """Many tenants × two backends × random chunk sizes, with every third
    launch failing once (transient device fault): the in-place retry must
    lose/duplicate NOTHING — per-future results and final outputs stay
    bitwise-equal to each tenant's offline engine."""
    injected = {"n": 0}
    attempted = {}                                   # id(batch) → batch ref
    orig_execute = MicroBatcher.execute

    def flaky_execute(self, batch):
        if id(batch) not in attempted:
            attempted[id(batch)] = batch             # strong ref: stable ids
            injected["n"] += 1
            if injected["n"] % 3 == 0:
                raise RuntimeError("injected transient device fault")
        return orig_execute(self, batch)

    monkeypatch.setattr(MicroBatcher, "execute", flaky_execute)
    n_per_backend, n_syms = 3, 311
    with AsyncServeRuntime(BatchPolicy(max_batch=3, max_wait_s=1e9),
                           launch_retries=2) as rt:
        specs = [_spec(f"st-{b}-{i}", b, seed=120 + 10 * j + i)
                 for j, b in enumerate(("fused_fp32", "fused_int8"))
                 for i in range(n_per_backend)]
        rng = np.random.default_rng(59)
        waves = {s.tenant_id:
                 rng.standard_normal(n_syms * CFG.n_os).astype(np.float32)
                 for s in specs}
        for s in specs:
            rt.open(s)
        futs = {s.tenant_id: [] for s in specs}
        streams = {s.tenant_id: chop(waves[s.tenant_id], 200, seed=i,
                                     jitter=0.9)
                   for i, s in enumerate(specs)}
        iters = {t: iter(c) for t, c in streams.items()}
        live = set(iters)
        while live:
            for t in list(live):
                c = next(iters[t], None)
                f = rt.submit(t, c) if c is not None else rt.finish(t)
                if c is None:
                    live.discard(t)
                if f is not None:
                    futs[t].append(f)
        rt.drain()
        assert injected["n"] >= 3                    # faults really fired
        assert not rt.errors                         # …but none terminal
        for s in specs:
            want = _offline(s, waves[s.tenant_id])
            got = rt.output(s.tenant_id)
            np.testing.assert_array_equal(got, want)  # no loss, no dup
            parts = [f.result(timeout=10) for f in futs[s.tenant_id]]
            np.testing.assert_array_equal(np.concatenate(parts), want)


def test_async_cancelled_future_does_not_poison_batch():
    """A caller may cancel() a pending chunk future; the symbols still
    join the stream and the OTHER tenants in the batch are untouched."""
    with AsyncServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9)) as rt:
        a = _spec("canc-a", "fused_fp32", seed=150)
        b = _spec("canc-b", "fused_fp32", seed=151)
        rng = np.random.default_rng(71)
        # ≥ one tile of positions (tile_m=32 → 512 syms) so the offline
        # call tiles exactly like serve (chunker docstring boundary note)
        wa = rng.standard_normal(600 * CFG.n_os).astype(np.float32)
        wb = rng.standard_normal(600 * CFG.n_os).astype(np.float32)
        rt.open(a)
        rt.open(b)
        fa = rt.submit("canc-a", wa)       # 1st of 2 → stays pending
        assert fa is not None
        fa.cancel()                        # legal caller-side abandonment
        fb = rt.submit("canc-b", wb)       # completes the batch → launch
        rt.drain()
        assert not rt.errors               # no InvalidStateError poisoning
        np.testing.assert_array_equal(fb.result(timeout=10),
                                      rt.output("canc-b"))
        # cancelled tenant's stream is still complete (data not dropped)
        got = rt.close("canc-a")
        np.testing.assert_array_equal(got, _offline(a, wa))


def test_async_terminal_failure_poisons_stream(monkeypatch):
    """A launch that fails beyond launch_retries fails the chunk future and
    poisons the session: output()/close() raise instead of returning a
    stream with a silent hole."""
    def dead_execute(self, batch):
        raise RuntimeError("dead device")

    monkeypatch.setattr(MicroBatcher, "execute", dead_execute)
    with AsyncServeRuntime(BatchPolicy(max_batch=1, max_wait_s=1e9),
                           launch_retries=1) as rt:
        rt.open(_spec("doomed", "fused_fp32", seed=130))
        rng = np.random.default_rng(61)
        fut = rt.submit(
            "doomed", rng.standard_normal(200 * CFG.n_os).astype(np.float32))
        rt.drain()
        assert rt.errors
        with pytest.raises(RuntimeError, match="dead device"):
            fut.result(timeout=10)
        with pytest.raises(RuntimeError, match="lost a chunk"):
            rt.output("doomed")


def test_async_close_waits_for_inflight_and_shutdown_rejects():
    rt = AsyncServeRuntime(BatchPolicy(max_batch=4, max_wait_s=1e9))
    try:
        spec = _spec("closer", "fused_fp32", seed=140)
        rt.open(spec)
        rng = np.random.default_rng(67)
        wave = rng.standard_normal(600 * CFG.n_os).astype(np.float32)
        for c in chop(wave, 300, seed=5):
            rt.submit("closer", c)
        got = rt.close("closer")                     # schedules + waits
        np.testing.assert_array_equal(got, _offline(spec, wave))
        assert "closer" not in rt.sessions
    finally:
        rt.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        rt.submit("closer", np.zeros(4, np.float32))


# ---------------------------------------------------------------------------
# chunker carry snapshot / restore — the failover primitive
# ---------------------------------------------------------------------------

def test_chunker_snapshot_restore_replays_identical_plans():
    """A chunker restored from a snapshot plans the SAME launches — same
    skip/n_emit, bitwise-identical input rows — as the original from that
    point on, and discards anything pushed after the snapshot."""
    rng = np.random.default_rng(11)
    ch = StreamChunker(halo=68, total_stride=2, tile_m=8)
    ch.push(rng.standard_normal(500).astype(np.float32))
    p = ch.plan()
    ch.commit(p)
    snap = ch.snapshot()
    tail = rng.standard_normal(300).astype(np.float32)

    def play(c):
        c.push(tail)
        c.finish()
        plans = []
        while True:
            pl = c.plan()
            if pl is None:
                break
            c.commit(pl)
            plans.append(pl)
        return plans

    first = play(ch)
    assert first, "stream must have emittable tail positions"
    fresh = StreamChunker(halo=68, total_stride=2, tile_m=8)
    fresh.push(np.full(999, 7.0, np.float32))      # pre-restore garbage
    fresh.restore(snap)
    second = play(fresh)
    assert len(first) == len(second)
    for a, b in zip(first, second):
        assert (a.skip, a.n_emit) == (b.skip, b.n_emit)
        np.testing.assert_array_equal(a.data, b.data)


@pytest.mark.parametrize("backend",
                         [b for b in BACKENDS if b.startswith("fused")])
def test_chunker_snapshot_restore_across_engine_rebuild(backend):
    """Failover round-trip per fused backend: snapshot the carry
    mid-stream, take a detour (extra pushed samples), restore, drop the
    pool entry so the engine REBUILDS from the spec — the finished stream
    is bitwise-equal to the uninterrupted offline equalization."""
    spec = _spec("snap", backend, seed=21)
    rt = ServeRuntime(BatchPolicy(max_batch=1, max_wait_s=0.0))
    s = rt.open(spec)
    rng = np.random.default_rng(7)
    wave = rng.standard_normal(400 * CFG.n_os).astype(np.float32)
    chunks = list(chop(wave, 300, seed=3))
    half = len(chunks) // 2
    for c in chunks[:half]:
        rt.submit("snap", c)
    snap = s.chunker.snapshot()
    emitted = s.chunker.emitted_positions
    s.chunker.push(rng.standard_normal(64).astype(np.float32))  # detour
    s.chunker.restore(snap)
    assert s.chunker.emitted_positions == emitted
    rt.pool.drop("snap")                 # force rebuild from TenantSpec
    for c in chunks[half:]:
        rt.submit("snap", c)
    got = rt.close("snap")
    np.testing.assert_array_equal(got, _offline(spec, wave))


@pytest.mark.parametrize("halo,ts,tile_m", [(9, 4, 8), (68, 2, 8)])
@pytest.mark.parametrize("cut", [0, 3, 17, 150])
def test_chunker_snapshot_round_trips_at_arbitrary_points(halo, ts, tile_m,
                                                          cut):
    """snapshot()/restore() round-trips at ARBITRARY mid-stream sample
    counts — including sub-receptive-field carries (cut < halo, where the
    buffer holds fewer samples than one output window needs) — and the
    restored chunker's remaining plan stream is identical to the original
    fed the same tail. The fleet migration path leans on exactly this:
    a snapshot taken wherever death struck must resume bit-exactly."""
    rng = np.random.default_rng(cut + halo)
    total = 600
    stream = rng.standard_normal(total).astype(np.float32)
    ch = StreamChunker(halo=halo, total_stride=ts, tile_m=tile_m)
    ch.push(stream[:cut])
    while True:                      # drain what's emittable pre-snapshot
        p = ch.plan()
        if p is None:
            break
        ch.commit(p)
    snap = ch.snapshot()
    assert snap.o_pos % tile_m == 0          # carry trim is tile-aligned
    assert snap.o_pos <= snap.next_pos
    other = StreamChunker(halo=halo, total_stride=ts, tile_m=tile_m)
    other.push(np.full(321, -3.0, np.float32))   # stale pre-restore state
    other.restore(snap)
    assert other.emitted_positions == ch.emitted_positions
    assert other.carry_samples == ch.carry_samples

    def play(c):
        c.push(stream[cut:])
        c.finish()
        out = []
        while True:
            p = c.plan()
            if p is None:
                break
            c.commit(p)
            out.append(p)
        return out

    first, second = play(ch), play(other)
    assert len(first) == len(second)
    for a, b in zip(first, second):
        assert (a.skip, a.n_emit) == (b.skip, b.n_emit)
        np.testing.assert_array_equal(a.data, b.data)
    # nothing lost, nothing duplicated: the full stream was emitted
    assert ch.emitted_positions == total // ts
    assert other.emitted_positions == total // ts
