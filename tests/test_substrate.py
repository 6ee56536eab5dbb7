"""Substrate tests: checkpoint manager, fault loop, straggler monitor,
data pipeline determinism, gradient compression numerics, roofline parser."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import PipelineConfig, TokenSource
from repro.launch import roofline as rl
from repro.optim import AdamW, grad_comp
from repro.runtime import (FailureInjector, StragglerConfig,
                           StragglerMonitor, TrainLoopConfig, WorkerFailure,
                           run_with_restarts)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree(x=1.0):
    return {"layers": {"w": jnp.full((4, 4), x), "b": jnp.zeros((4,))},
            "step_count": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep_k=2)
    ck.save(10, _tree(3.0), extra={"loss": 1.5})
    out = ck.restore(_tree(0.0))
    np.testing.assert_array_equal(np.asarray(out["layers"]["w"]), 3.0)
    assert int(out["step_count"]) == 7
    assert ck.extra(10)["loss"] == 1.5


def test_checkpoint_keep_k_and_latest(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep_k=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(float(s)))
    assert ck.steps() == [3, 4]
    assert ck.latest_step() == 4
    out = ck.restore(_tree(0.0))
    np.testing.assert_array_equal(np.asarray(out["layers"]["w"]), 4.0)


def test_checkpoint_atomicity_no_tmp_left(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep_k=3)
    ck.save(1, _tree())
    assert not list(tmp_path.glob("*.tmp"))
    # a stale tmp dir from a crashed save is ignored and overwritten
    (tmp_path / "step_00000002.tmp").mkdir()
    ck.save(2, _tree(2.0))
    out = ck.restore(_tree(0.0), step=2)
    np.testing.assert_array_equal(np.asarray(out["layers"]["w"]), 2.0)


# ---------------------------------------------------------------------------
# fault loop
# ---------------------------------------------------------------------------

def test_run_with_restarts_recovers(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep_k=3)
    calls = {"n": 0}

    def init_state():
        return jnp.zeros(()), jnp.zeros(())

    def train_step(p, o, batch):
        calls["n"] += 1
        return p + 1, o, {"loss": jnp.asarray(1.0) / (p + 1)}

    def batches(start):
        def gen():
            while True:
                yield {}
        return gen()

    inj = FailureInjector(fail_at=(7, 13))
    out = run_with_restarts(
        TrainLoopConfig(total_steps=20, checkpoint_every=5, log_every=5),
        ck, init_state, train_step, batches, injector=inj)
    assert out["steps"] == 20
    assert out["restarts"] == 2
    assert float(out["final"][0]) == 20.0        # params resumed, not reset


def test_run_with_restarts_gives_up(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep_k=3)
    inj = FailureInjector(fail_at=(1,))
    inj._fired = set()          # always fire

    class AlwaysFail(FailureInjector):
        def check(self, step):
            if step == 1:
                raise WorkerFailure("persistent")

    with pytest.raises(WorkerFailure):
        run_with_restarts(
            TrainLoopConfig(total_steps=5, checkpoint_every=100,
                            max_restarts=2),
            ck, lambda: (jnp.zeros(()), jnp.zeros(())),
            lambda p, o, b: (p + 1, o, {"loss": jnp.zeros(())}),
            lambda s: iter(lambda: {}, None), injector=AlwaysFail())


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------

def test_straggler_detection_and_mitigation():
    fired = []
    mon = StragglerMonitor(
        StragglerConfig(warmup_steps=3, patience=2, sigma_factor=3.0),
        on_straggler=lambda step, dt: fired.append(step))
    for s in range(20):
        mon.observe(s, 0.10 + 0.001 * (s % 3))
    assert not mon.flags
    # inject persistent 10× steps
    flagged = [mon.observe(100 + i, 1.0) for i in range(3)]
    assert all(flagged)
    assert fired, "mitigation callback not fired"
    assert mon.recommend_accum(8) == 4
    sm = mon.summary()
    assert sm["flagged"] >= 2 and sm["p50_s"] < 0.2


def test_straggler_stats_robust_to_outliers():
    mon = StragglerMonitor(StragglerConfig(warmup_steps=2, patience=100))
    for s in range(10):
        mon.observe(s, 0.1)
    mean_before = mon.mean
    mon.observe(10, 5.0)            # flagged → excluded from stats
    assert mon.mean == pytest.approx(mean_before)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_token_source_deterministic_and_elastic():
    cfg = PipelineConfig(seq_len=128, global_batch=8, seed=5)
    src = TokenSource(cfg, vocab=1000)
    a = src.block(step=3, row=2)
    b = src.block(step=3, row=2)
    np.testing.assert_array_equal(a, b)              # restart-stable
    c = src.block(step=3, row=3)
    assert not np.array_equal(a, c)                  # rows differ
    d = src.block(step=4, row=2)
    assert not np.array_equal(a, d)                  # steps differ
    assert a.min() >= 0 and a.max() < 1000


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compress_roundtrip_error_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (512,))
    p, s = grad_comp.compress(g)
    back = grad_comp.decompress(p, s)
    assert p.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) / 2 + 1e-6


def test_error_feedback_mean_converges():
    """EF property: the RUNNING SUM of decompressed grads tracks the true
    sum (error never accumulates unboundedly)."""
    key = jax.random.PRNGKey(1)
    err = {"w": jnp.zeros((64,))}
    total_true = jnp.zeros((64,))
    total_sent = jnp.zeros((64,))
    for i in range(50):
        key, k = jax.random.split(key)
        g = {"w": jax.random.normal(k, (64,))}
        payload, scale, err = grad_comp.ef_compress_tree(g, err)
        total_sent += grad_comp.decompress(payload["w"], scale["w"])
        total_true += g["w"]
    # the residual is the CURRENT error buffer, bounded by one quant step
    resid = np.asarray(total_true - total_sent)
    np.testing.assert_allclose(resid, np.asarray(err["w"]), atol=1e-4)
    assert np.max(np.abs(resid)) < 0.05


# ---------------------------------------------------------------------------
# roofline HLO parser
# ---------------------------------------------------------------------------

_FAKE_HLO = """\
HloModule jit_step

%body (param: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %g = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %ag = f32[128,8]{1,0} all-gather(%g), channel_id=1, replica_groups=[16,16]<=[256]T(1,0), dimensions={0}
  %d = f32[8,8]{1,0} dot(%g, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%g, %d)
}

%cond (param.1: (s32[], f32[8,8])) -> pred[] {
  %p1 = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %init = (s32[], f32[8,8]) tuple(%a, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %ar = f32[8,8]{1,0} all-reduce(%a), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_roofline_parser_loops_and_collectives():
    an = rl.analyze_hlo(_FAKE_HLO)
    # dot inside while body: 2·8·8·8 flops × trip 10
    assert an.flops == pytest.approx(2 * 8 * 8 * 8 * 10)
    # all-gather operand 256B × 10 trips; all-reduce 256B × 1
    assert an.coll.op_bytes["all-gather"] == 256 * 10
    assert an.coll.op_bytes["all-reduce"] == 256
    assert an.coll.count["all-gather"] == 10
    # ring models: AG receives (n−1)·operand; AR moves 2·(n−1)/n·operand
    assert an.coll.ring_bytes["all-gather"] == pytest.approx(
        256 * 15 * 10)
    assert an.coll.ring_bytes["all-reduce"] == pytest.approx(
        2 * 256 * 3 / 4)


def test_roofline_terms_and_bottleneck():
    coll = rl.CollectiveStats({"all-reduce": 100}, {"all-reduce": 1e9}, {})
    r = rl.Roofline(flops=197e12, hbm_bytes=0.0, coll=coll, n_chips=4,
                    model_flops=4 * 197e12 * 0.5)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(
        1e9 / rl.peaks(rl.V5E)["ici_link_bytes_per_s"])
    assert r.bottleneck == "compute"
    assert r.mfu == pytest.approx(0.5)


def test_roofline_peaks_table_rejects_unknown_device():
    assert rl.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(ValueError, match="no published peaks"):
        rl.peaks("cpu")
    coll = rl.CollectiveStats({}, {}, {})
    r = rl.Roofline(flops=1.0, hbm_bytes=1.0, coll=coll, n_chips=1,
                    device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        r.t_compute


def test_straggler_warmup_never_flags_and_is_excluded_from_quantiles():
    """Warmup steps carry compile/first-touch time: they must neither
    flag (even when enormous) nor skew the summary quantiles."""
    mon = StragglerMonitor(StragglerConfig(warmup_steps=4, patience=1,
                                           sigma_factor=1.0))
    flagged = [mon.observe(s, 50.0) for s in range(4)]   # huge warmups
    assert not any(flagged) and not mon.flags and not mon.degraded
    for s in range(4, 14):
        mon.observe(s, 0.01)
    sm = mon.summary()
    assert sm["steps"] == 14 and sm["flagged"] == 0
    assert sm["p50_s"] <= 0.011 and sm["p99_s"] <= 0.011  # no 50s leak


def test_straggler_latch_edges_fire_callbacks_exactly_once():
    """patience=2 edge walk: the first flag does nothing, the second
    latches (on_straggler fires ONCE), further flags while degraded stay
    silent, and exactly `patience` consecutive clean steps un-latch
    (on_recovered fires once)."""
    events = []
    mon = StragglerMonitor(
        StragglerConfig(warmup_steps=2, patience=2, sigma_factor=3.0),
        on_straggler=lambda step, dt: events.append(("slow", step)),
        on_recovered=lambda step: events.append(("ok", step)))
    for s in range(8):                       # warmup + steady baseline
        mon.observe(s, 0.01)
    assert mon.observe(8, 1.0) and not mon.degraded      # flag 1 of 2
    assert events == []
    assert mon.observe(9, 1.0) and mon.degraded          # latch
    assert events == [("slow", 9)]
    assert mon.observe(10, 1.0) and mon.degraded         # no refire
    assert events == [("slow", 9)]
    mon.observe(11, 0.01)                    # clean 1 of 2: still latched
    assert mon.degraded
    mon.observe(12, 0.01)                    # clean 2: un-latch
    assert not mon.degraded
    assert events == [("slow", 9), ("ok", 12)]
    assert mon.recommend_accum(8) == 8       # mitigation lifted


def test_straggler_non_consecutive_flags_never_latch():
    mon = StragglerMonitor(StragglerConfig(warmup_steps=2, patience=2,
                                           sigma_factor=3.0))
    for s in range(6):
        mon.observe(s, 0.01)
    for i in range(5):                       # flag/clean alternation
        assert mon.observe(6 + 2 * i, 1.0)
        assert not mon.degraded
        assert not mon.observe(7 + 2 * i, 0.01)
    assert not mon.degraded and len(mon.flags) == 5
