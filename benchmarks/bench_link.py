"""Link-quality estimation + SLO closed loop — the signal-health gate.

The system metrics see launches and latencies; `repro.obs.link` watches
the SIGNAL. This bench runs the whole quality-degradation story on the
serving runtime and records, in `BENCH_link.json` at the repo root, one
HARD host-independent criterion (`criteria.link_ok`) with three parts:

  * tracking — a TRACK tenant serves through an AWGN-only channel
    (identity taps, noise-dominated operating point — see
    `_track_channel`) whose SNR ramps down 4 dB: the decision-directed
    `LinkMonitor` SNR estimate must follow the true channel ramp
    (Pearson correlation ≥ `CORR_FLOOR` over the burst trajectory, and
    the estimate must fall by ≥ `DROP_FLOOR_DB`). This is the "estimator
    sees the channel, not the host" check.
  * closed loop — an ADAPT tenant serves through the tap-rotation drift
    (SNR held constant, so recovery is possible): an `SloEngine` rule on
    `link.{tenant}.snr_db` must LATCH a breach during the degradation,
    the breach edge must trigger `OnlineAdapter.request_adapt` (the
    fine-tune cadence is set effectively infinite — adaptation here is
    PURELY event-driven), the promotion must call back into
    `SloEngine.resolve`, and the alert must stay clear to the end of the
    run (the recovered estimate sits back above the threshold).
  * bitwise — serving with link estimation AND tracing AND the SLO
    engine all ON must equal offline equalization bit-for-bit on every
    fused backend (fp32 / bf16 / int8) — contract #11 extended:
    observation of the signal plane never changes the signal.

All three parts are deterministic under the fixed seeds — `--check`
fails hard if any breaks. No throughput rates are tracked (estimation
is host-side numpy).
"""
from __future__ import annotations

import json
import pathlib
from typing import Optional

import jax
import numpy as np

from repro.adapt import (AdaptPolicy, FineTuneConfig, OnlineAdapter,
                         PromotionPolicy)
from repro.channels.drift import DriftingProakis, DriftSchedule
from repro.channels.proakis import ProakisConfig
from repro.core import equalizer as eq
from repro.core.train_eq import EqTrainConfig, train_equalizer
from repro.obs import LinkMonitor, Observability, SloEngine, SloRule
from repro.serve import BatchPolicy, ServeRuntime, TenantSpec, chop

from .common import Bench

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_PATH = REPO_ROOT / "BENCH_link.json"

CFG = eq.CNNEqConfig()
TILE_M = 16
SYMS_PER_BURST = 2048
SCHEDULE = DriftSchedule(hold_bursts=4, ramp_bursts=6)
N_BURSTS = 20
FT = FineTuneConfig(steps=200, batch=8, seq_syms=256, lr=3e-3)

CORR_FLOOR = 0.8           # est-vs-true SNR Pearson corr over the bursts
DROP_FLOOR_DB = 2.0        # the 4 dB true ramp must show as >= this
SLO_MARGIN_DB = 2.0        # breach threshold below the pre-drift estimate

# bitwise-parity workload
INT8_FMT = tuple((2, 5, 3, 4) for _ in range(CFG.layers))
PAR_SYMS = 480
PAR_CHUNK = 120


def _adapt_policy() -> AdaptPolicy:
    # adapt_every_syms effectively infinite: fine-tuning fires ONLY via
    # request_adapt (the SLO breach hook) — the event-driven claim
    return AdaptPolicy(
        min_train_syms=3072, adapt_every_syms=1 << 30, eval_capacity=8192,
        promotion=PromotionPolicy(min_eval_syms=1024, eval_bucket_syms=512))


def _track_channel() -> DriftingProakis:
    """AWGN-only Proakis (identity taps) at a noise-dominated operating
    point: the equalizer's residual is mostly channel noise, so the true
    SNR ramp must show through in the decision-directed estimate. (On the
    full Proakis-B ISI channel the CNN's residual is ISI-dominated and a
    4 dB noise ramp moves the output SNR by well under 1 dB — a tracking
    gate there would test the equalizer, not the estimator.)"""
    return DriftingProakis(cfg=ProakisConfig(snr_db=14.0),
                           taps_from=(1.0, 0.0, 0.0),
                           taps_to=(1.0, 0.0, 0.0),
                           snr_delta_db=-4.0)


def _drift_phase(track_pb, adapt_pb):
    """The two-tenant drift scenario on one observed runtime."""
    ch_snr = _track_channel()                        # SNR ramp only
    ch_rot = DriftingProakis(snr_delta_db=0.0)       # tap rotation only

    obs = Observability(tracing=True)
    slo = SloEngine(obs)
    link = LinkMonitor(obs, slo=slo)
    rt = ServeRuntime(BatchPolicy(max_batch=2, max_wait_s=1e9),
                      obs=obs, link=link)
    adapter = OnlineAdapter(rt, _adapt_policy(), FT)

    # breach edge → event-driven fine-tune; promotion → alert retired
    def on_breach(tenant, rule, value):
        if tenant in adapter.tenants:
            adapter.request_adapt(tenant)

    slo.on_breach = on_breach
    adapter.on_promoted = lambda tid: slo.resolve(tid)

    rt.open(TenantSpec("track", CFG, params=track_pb[0],
                       bn_state=track_pb[1],
                       backend="fused_fp32", tile_m=TILE_M))
    adapter.attach(TenantSpec("adapt", CFG, params=adapt_pb[0],
                              bn_state=adapt_pb[1],
                              backend="fused_fp32", tile_m=TILE_M))

    key = jax.random.PRNGKey(3)
    est_track, est_adapt, true_snr = [], [], []
    for b in range(N_BURSTS):
        t = SCHEDULE.t_at(b)
        for i, (tid, ch) in enumerate((("track", ch_snr),
                                       ("adapt", ch_rot))):
            rx, syms = ch.at(t)(jax.random.fold_in(key, 2 * b + i),
                                SYMS_PER_BURST)
            if tid == "adapt":
                adapter.feed_pilots(tid, np.asarray(syms))
            rt.submit(tid, np.asarray(rx))
        rt.drain()
        est_track.append(link.estimate("track").snr_db)
        est_adapt.append(link.estimate("adapt").snr_db)
        true_snr.append(ch_snr.snr_at(t))
        if b == SCHEDULE.hold_bursts - 1:
            # threshold pinned to the MEASURED pre-drift estimate, so the
            # rule is host-independent and survives retraining drift
            thresh = min(est_track[-1], est_adapt[-1]) - SLO_MARGIN_DB
            slo.add_rule(SloRule(
                "snr_floor", "link.{tenant}.snr_db", threshold=thresh,
                direction="below", min_samples=SYMS_PER_BURST,
                samples="link.{tenant}.syms", patience=2))
        if slo.breached("adapt"):
            adapter.request_adapt("adapt")   # keep asking until promoted
        adapter.step("adapt")
    rt.close("track")
    rt.close("adapt")
    return {
        "true_snr_db": true_snr, "est_track_db": est_track,
        "est_adapt_db": est_adapt,
        "threshold_db": next((r.threshold for r in slo.rules), None),
        "alerts": [dict(a) for a in slo.alerts],
        "actions": [r.action for r in adapter.history
                    if r.action != "idle"],
        "breached_final": slo.breached("adapt"),
        "promotions": sum(r.action == "promoted" for r in adapter.history),
    }


def _weights(seed: int):
    params = eq.init(jax.random.PRNGKey(seed), CFG)
    folded = eq.fold_bn(params, eq.init_bn_state(CFG), CFG)
    return eq.folded_weights(folded)


def _parity_phase() -> dict:
    """Serve all three fused backends with link + SLO + tracing ON and
    demand bitwise equality with offline (contract #11 extended)."""
    import jax.numpy as jnp

    specs = []
    for i, backend in enumerate(("fused_fp32", "fused_bf16", "fused_int8")):
        specs.append(TenantSpec(
            f"p{i}", CFG, weights=_weights(600 + i),
            formats=INT8_FMT if backend == "fused_int8" else None,
            backend=backend, tile_m=32))
    rng = np.random.default_rng(11)
    waves = {s.tenant_id: rng.standard_normal(
        (PAR_SYMS + 16 * i) * CFG.n_os).astype(np.float32)
        for i, s in enumerate(specs)}
    offline = {s.tenant_id: np.asarray(
        s.build_engine()(jnp.asarray(waves[s.tenant_id][None])))[0]
        for s in specs}

    obs = Observability(tracing=True)
    slo = SloEngine(obs, rules=(SloRule(
        "snr_floor", "link.{tenant}.snr_db", threshold=5.0),))
    link = LinkMonitor(obs, slo=slo)
    rt = ServeRuntime(BatchPolicy(max_batch=3, max_wait_s=1e9),
                      obs=obs, link=link)
    for s in specs:
        rt.open(s)
    streams = {t: iter(chop(w, PAR_CHUNK * CFG.n_os, seed=i, jitter=0.5))
               for i, (t, w) in enumerate(sorted(waves.items()))}
    live = set(streams)
    while live:
        for t in sorted(live):
            c = next(streams[t], None)
            if c is None:
                live.discard(t)
                rt.finish(t)
            else:
                rt.submit(t, c)
    rt.drain()
    per_backend = {
        s.backend: bool(np.array_equal(rt.output(s.tenant_id),
                                       offline[s.tenant_id]))
        for s in specs}
    return {"per_backend": per_backend,
            "syms_estimated": int(sum(
                link.estimate(s.tenant_id).syms for s in specs)),
            "bitwise": all(per_backend.values())}


def run(train_steps: int = 500,
        out_path: Optional[pathlib.Path] = OUT_PATH) -> dict:
    bench = Bench("link_slo", "signal health: link estimators + SLO loop")

    tcfg = EqTrainConfig(steps=train_steps, eval_syms=1 << 14)
    params_a, bn_a, info_a = train_equalizer(
        jax.random.PRNGKey(0), "cnn",
        CFG, DriftingProakis().at(0.0), tcfg)
    params_t, bn_t, info_t = train_equalizer(
        jax.random.PRNGKey(0), "cnn",
        CFG, _track_channel().at(0.0), tcfg)
    print(f"[bench_link] trained: adapt tenant pre-drift BER "
          f"{float(info_a['ber']):.3e}, track tenant "
          f"{float(info_t['ber']):.3e}")

    drift = _drift_phase((params_t, bn_t), (params_a, bn_a))
    est_t = np.asarray(drift["est_track_db"])
    true_t = np.asarray(drift["true_snr_db"])
    corr = float(np.corrcoef(est_t, true_t)[0, 1])
    pre = float(np.mean(est_t[:SCHEDULE.hold_bursts]))
    drop = pre - float(est_t[-1])
    states = [a["state"] for a in drift["alerts"]
              if a["tenant"] == "adapt"]
    breach_fired = "breach" in states
    resolved = "resolved" in states
    promoted = drift["promotions"] >= 1
    final_clear = not drift["breached_final"]
    print(f"[bench_link] tracking: corr {corr:.3f} (floor {CORR_FLOOR}), "
          f"est drop {drop:.2f} dB (floor {DROP_FLOOR_DB}, true 4.00)")
    print(f"[bench_link] closed loop: breach_fired={breach_fired} "
          f"promoted={promoted} resolved={resolved} "
          f"final_clear={final_clear} "
          f"(actions {drift['actions']})")

    parity = _parity_phase()
    print(f"[bench_link] parity with link+slo+tracing ON: "
          f"{parity['per_backend']}")

    criteria = {
        "snr_corr": corr,
        "snr_est_drop_db": drop,
        "tracking_ok": bool(corr >= CORR_FLOOR and drop >= DROP_FLOOR_DB),
        "breach_fired": bool(breach_fired),
        "promoted": bool(promoted),
        "resolved": bool(resolved),
        "final_clear": bool(final_clear),
        "bitwise": bool(parity["bitwise"]),
        "link_ok": bool(corr >= CORR_FLOOR and drop >= DROP_FLOOR_DB
                        and breach_fired and promoted and resolved
                        and final_clear and parity["bitwise"]),
    }
    print(f"[bench_link] link_ok={criteria['link_ok']}")

    report = {
        "backend_default": jax.default_backend(),
        "scenario": {
            "n_bursts": N_BURSTS, "syms_per_burst": SYMS_PER_BURST,
            "hold_bursts": SCHEDULE.hold_bursts,
            "ramp_bursts": SCHEDULE.ramp_bursts,
            "train_steps": train_steps,
            "snr_ramp_db": -4.0,
            "slo_margin_db": SLO_MARGIN_DB,
            "fine_tune": {"steps": FT.steps, "lr": FT.lr,
                          "seq_syms": FT.seq_syms},
        },
        "drift": drift,
        "parity": parity,
        "criteria": criteria,
    }
    if out_path is not None:
        out_path.write_text(json.dumps(report, indent=2))
        print(f"[bench_link] wrote {out_path}")
    bench.record("report", report)
    return bench.finish()


if __name__ == "__main__":
    run()
