"""System behaviour of the equalizer stack: topology, BN folding, stream
partitioning, timing model, sequence-length framework, channels."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channels import imdd, proakis
from repro.channels.common import (ber_from_soft, bits_to_pam,
                                   pam_constellation, pam_decision)
from repro.core import equalizer as eq
from repro.core import seqlen_opt, stream_partition as sp, timing_model as tm

KEY = jax.random.PRNGKey(0)
PAPER_CFG = eq.CNNEqConfig()          # V_p=8, L=3, K=9, C=5, N_os=2


# ---------------------------------------------------------------------------
# topology / formulas (paper §3)
# ---------------------------------------------------------------------------

def test_paper_topology_shapes():
    params = eq.init(KEY, PAPER_CFG)
    assert params["conv"][0]["w"].shape == (5, 1, 9)
    assert params["conv"][1]["w"].shape == (5, 5, 9)
    assert params["conv"][2]["w"].shape == (8, 5, 9)
    x = jnp.zeros((4096 * 2,))
    y, _ = eq.apply(params, x, PAPER_CFG, train=True,
                    bn_state=eq.init_bn_state(PAPER_CFG))
    assert y.shape == (4096,)          # one estimate per symbol


def test_mac_per_symbol_formula():
    """MAC_sym = K·C/V_p + (L−2)·K·C²/V_p + K·C/N_os  (paper §3.5)."""
    c = PAPER_CFG
    want = 9 * 5 / 8 + 1 * 9 * 5 * 5 / 8 + 9 * 5 / 2
    assert c.mac_per_symbol() == pytest.approx(want)
    assert c.mac_per_symbol() == pytest.approx(56.25)


def test_receptive_field_formula():
    """o_sym = (K−1)(1+V_p(L−1))/2 (paper §6.1)."""
    assert sp.overlap_symbols(PAPER_CFG) == (9 - 1) * (1 + 8 * 2) // 2 == 68


def test_actual_overlap_paper():
    """o_act = nextEven(⌈o_sym/(V_p·N_i)⌉)·V_p·N_i."""
    o = sp.actual_overlap(PAPER_CFG, 64)
    assert o % (8 * 64) == 0 and o >= sp.overlap_symbols(PAPER_CFG)
    assert o == 2 * 8 * 64            # nextEven(1)=2 → 1024 symbols


def test_bn_fold_matches_eval():
    cfg = PAPER_CFG
    params = eq.init(KEY, cfg)
    bn = eq.init_bn_state(cfg)
    bn = {"bn": [{"mean": 0.3 * jnp.ones_like(s["mean"]),
                  "var": 1.7 * jnp.ones_like(s["var"])} for s in bn["bn"]]}
    x = jax.random.normal(KEY, (2, 512))
    y_eval, _ = eq.apply(params, x, cfg, train=False, bn_state=bn)
    y_fold = eq.apply_folded(eq.fold_bn(params, bn, cfg), x, cfg)
    np.testing.assert_allclose(np.asarray(y_eval), np.asarray(y_fold),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# stream partitioning (paper §5.3): N_i instances == 1 instance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_inst", [2, 4, 8])
def test_partitioned_equals_unsplit_interior(n_inst):
    cfg = PAPER_CFG
    params = eq.init(KEY, cfg)
    folded = eq.fold_bn(params, eq.init_bn_state(cfg), cfg)
    apply_fn = lambda chunks: eq.apply_folded(folded, chunks, cfg)

    n_syms = 512 * n_inst
    x = jax.random.normal(KEY, (n_syms * cfg.n_os,))
    y_split = sp.partitioned_apply(apply_fn, x, n_inst, cfg)
    y_full = apply_fn(x[None])[0]
    assert y_split.shape == y_full.shape
    o = sp.overlap_symbols(cfg)
    # Interior: identical (the overlap covers the receptive field). The
    # outer o_sym symbols of the WHOLE stream differ by padding scheme
    # (per-layer SAME vs one-shot OGM zero-pad) — the FPGA pipeline's cold
    # start, outside the paper's equality claim.
    np.testing.assert_allclose(np.asarray(y_split)[o:-o],
                               np.asarray(y_full)[o:-o],
                               rtol=1e-4, atol=1e-4)
    # CHUNK BORDERS are interior symbols: verify the splices exactly
    # (this is the paper's "BER flat across the stream" property).
    l_inst = n_syms // n_inst
    for b in range(1, n_inst):
        lo, hi = b * l_inst - 100, b * l_inst + 100
        np.testing.assert_allclose(np.asarray(y_split)[lo:hi],
                                   np.asarray(y_full)[lo:hi],
                                   rtol=1e-4, atol=1e-4)


def _gather_split(x, n_inst, o_act, n_os):
    """The split as an index gather: chunk i is the zero-padded stream from
    i·ℓ, ℓ + 2o samples long."""
    l_samp, o_samp = x.shape[0] // n_inst, o_act * n_os
    xp = np.pad(x, (o_samp, o_samp))
    starts = np.arange(n_inst) * l_samp
    return xp[starts[:, None] + np.arange(l_samp + 2 * o_samp)]


@pytest.mark.parametrize("n_inst,l_inst,o_act,tail", [
    (64, 7320, 1024, 0),      # the HT deployment unit (reach 1)
    (1, 512, 80, 0),          # one instance: zero overlap both sides
    (16, 20, 96, 0),          # overlap past several neighbours (reach 5)
    (8, 16, 128, 0),          # reach 8
    (2, 512, 96, 0),          # test_partitioned_equals_unsplit_interior
    (4, 512, 128, 0),
    (8, 512, 128, 0),
    (4, 1024, 128, 0),        # test_partition_ber_flat_across_borders
    (8, 256, 128, 0),         # test_halo
    (8, 1024, 128, 0),        # test_engine's backend equivalence
    (4, 100, 40, 3),          # a stream that does not divide: tail samples
    (4, 100, 40, 90),         # feed only the last chunk's right overlap
], ids=lambda v: str(v))
def test_split_with_overlap_matches_gather_bitwise(n_inst, l_inst, o_act,
                                                   tail):
    """The static-slice split is pure data movement: it equals the index
    gather exactly, for any reach r = ⌈o/ℓ⌉."""
    n_os = PAPER_CFG.n_os
    rng = np.random.default_rng(n_inst * 7919 + l_inst + o_act + tail)
    x = rng.standard_normal(n_inst * l_inst * n_os + tail).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda v: sp.split_with_overlap(v, n_inst, o_act, n_os))(x))
    want = _gather_split(x, n_inst, o_act, n_os)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)


def test_partition_ber_flat_across_borders():
    """The paper's Fig-9 property: BER is not elevated at chunk borders."""
    cfg = PAPER_CFG
    ccfg = proakis.ProakisConfig(snr_db=25.0)
    rx, syms = proakis.simulate(KEY, ccfg, 4096)
    params = eq.init(KEY, cfg)
    folded = eq.fold_bn(params, eq.init_bn_state(cfg), cfg)
    apply_fn = lambda chunks: eq.apply_folded(folded, chunks, cfg)
    y = sp.partitioned_apply(apply_fn, rx, 4, cfg)
    # untrained CNN — we check only exactness vs the unsplit reference on
    # the interior (the stream's outer o_sym symbols differ by padding
    # scheme; see test_partitioned_equals_unsplit_interior)
    y_ref = apply_fn(rx[None])[0]
    o = sp.overlap_symbols(cfg)
    np.testing.assert_allclose(np.asarray(y)[o:-o],
                               np.asarray(y_ref)[o:-o], rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# timing model (paper §6.1, Fig. 12)
# ---------------------------------------------------------------------------

def test_timing_model_paper_numbers():
    cfg = PAPER_CFG
    hw = tm.fpga_profile(cfg, f_clk=200e6)
    # T_max = N_i·V_p·f_clk = 64·8·200MHz = 102.4 GSa/s ≈ 51.2 GBd
    assert tm.max_throughput(hw, 64) == pytest.approx(102.4e9)
    # the paper's framework picks ℓ_inst = 7320 for T_req = 80 GSym/s;
    # granularity differences allow ±1 grid step
    l_inst = seqlen_opt.optimal_l_inst(cfg, hw, 64, 80e9)
    assert abs(l_inst - 7320) <= 8
    # λ_sym at ℓ_inst: paper reports 17.5 µs
    lam = tm.symbol_latency(cfg, hw, 64, l_inst)
    assert lam == pytest.approx(17.5e-6, rel=0.05)
    # and the throughput constraint is met
    assert tm.net_throughput(cfg, hw, 64, l_inst) >= 80e9


def test_timing_monotonicity():
    cfg = PAPER_CFG
    hw = tm.fpga_profile(cfg)
    ls = [1024, 4096, 16384, 65536]
    tps = [tm.net_throughput(cfg, hw, 16, l) for l in ls]
    lats = [tm.symbol_latency(cfg, hw, 16, l) for l in ls]
    assert all(a < b for a, b in zip(tps, tps[1:]))        # T_net ↑ in ℓ
    assert all(a < b for a, b in zip(lats, lats[1:]))      # λ ↑ in ℓ
    assert tps[-1] < tm.max_throughput(hw, 16)             # saturates below T_max


def test_lut_generator():
    cfg = PAPER_CFG
    hw = tm.fpga_profile(cfg)
    lut = seqlen_opt.build_lut(cfg, hw, 64, [20e9, 40e9, 80e9])
    for t_req, choice in lut.items():
        assert choice.t_net >= t_req
        g = seqlen_opt.granularity(cfg, 64)
        assert choice.l_inst % g == 0
    # harder requirement ⇒ longer ℓ_inst ⇒ more latency
    assert lut[80e9].l_inst > lut[40e9].l_inst > lut[20e9].l_inst


def test_infeasible_t_req_raises():
    cfg = PAPER_CFG
    hw = tm.fpga_profile(cfg)
    with pytest.raises(ValueError):
        seqlen_opt.optimal_l_inst(cfg, hw, 4, 80e9)   # 4 instances can't


# ---------------------------------------------------------------------------
# channels (paper §2)
# ---------------------------------------------------------------------------

def test_imdd_is_nonlinear_channel():
    """CD + square-law ⇒ nonlinear ISI: the received samples at symbol
    instants are NOT an affine function of the transmitted amplitudes."""
    cfg = imdd.IMDDConfig(snr_db=60.0)          # noiseless, pure ISI
    rx, syms = imdd.simulate(KEY, cfg, 8192)
    assert rx.shape == (8192 * 2,)
    amps = np.asarray(bits_to_pam(syms, 2))
    samp = np.asarray(rx)[::2]
    # fit the best linear FIR (15 taps) from amps → samples; residual stays
    a = np.stack([np.roll(amps, s) for s in range(-7, 8)], 1)
    coef, *_ = np.linalg.lstsq(a[8:-8], samp[8:-8], rcond=None)
    resid = samp[8:-8] - a[8:-8] @ coef
    rel = np.var(resid) / np.var(samp)
    assert rel > 0.01, f"channel looks linear (rel resid {rel:.4f})"


def test_proakis_channel_shapes_and_stats():
    cfg = proakis.ProakisConfig()
    rx, syms = proakis.simulate(KEY, cfg, 4096)
    assert rx.shape == (8192,) and syms.shape == (4096,)
    assert abs(float(jnp.mean(rx))) < 1e-3
    assert float(jnp.std(rx)) == pytest.approx(1.0, abs=1e-3)


def test_pam_decision_roundtrip():
    for levels in (2, 4, 8):
        syms = jnp.arange(levels)
        amps = bits_to_pam(syms, levels)
        np.testing.assert_array_equal(np.asarray(pam_decision(amps, levels)),
                                      np.asarray(syms))
        c = pam_constellation(levels)
        assert float(jnp.mean(c ** 2)) == pytest.approx(1.0, rel=1e-5)


def test_ber_from_soft():
    y = jnp.asarray([1.0, -1.0, 1.0, -0.9])
    t = jnp.asarray([1, 0, 0, 0])
    assert float(ber_from_soft(y, t, 2)) == pytest.approx(0.25)
