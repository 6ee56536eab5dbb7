"""EqualizerEngine — the production inference path (core/engine.py).

Covers the ISSUE-1 acceptance surface:
  * fused_fp32 backend vs the pure-jnp oracle (`ref.cnn_eq`) across the two
    DOP operating points (equalizer_ht / equalizer_lp) and extra topologies,
    odd stream lengths, and tile-boundary cases — ≤2-ULP agreement (kernel
    and oracle add the same products in the same order, so only XLA FMA
    contraction differs);
  * fused_int8 backend vs the QAT fake-quant reference — within one
    accumulation LSB (observed: exact, integer arithmetic);
  * backend equivalence through `partitioned_apply` — the merged stream is
    identical across backends on the kept (interior) symbols;
  * backend selection (auto → int8 only when the learned formats deploy),
    and the tile_m autotune cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import equalizer_ht as HT
from repro.configs import equalizer_lp as LP
from repro.core import autotune
from repro.core import equalizer as eq
from repro.core import qat as qat_lib
from repro.core import stream_partition as sp
from repro.core.engine import BACKENDS, EqualizerEngine
from repro.kernels.cnn_eq import ref as cnn_ref

KEY = jax.random.PRNGKey(0)
ULP_TOL = 5e-6      # ~2 ULP of fp32 at the equalizer's output magnitudes

INT8_FMT = (2, 5, 3, 4)      # Q2.5 weights / Q3.4 activations — 8 bits each


def _engine(cfg, backend, tile_m=64, key=KEY, formats=None):
    params = eq.init(key, cfg)
    bn = {"bn": [{"mean": 0.1 * jax.random.normal(key, s["mean"].shape),
                  "var": 1.0 + 0.5 * jax.random.uniform(key, s["var"].shape)}
                 for s in eq.init_bn_state(cfg)["bn"]]}
    folded = eq.fold_bn(params, bn, cfg)
    engine = EqualizerEngine.from_folded(folded, cfg, backend=backend,
                                         tile_m=tile_m, formats=formats)
    return engine, folded


# ---------------------------------------------------------------------------
# fused_fp32 vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    HT.CNN,                                                  # §7.2 point
    eq.CNNEqConfig(layers=4, kernel=15, channels=4, v_parallel=4),
    eq.CNNEqConfig(layers=5, kernel=9, channels=5, v_parallel=16),
])
@pytest.mark.parametrize("n_syms", [1024, 1021, 257])        # odd lengths
def test_fused_fp32_matches_ref(cfg, n_syms):
    engine, folded = _engine(cfg, "fused_fp32", tile_m=16)
    weights = tuple((l["w"], l["b"]) for l in folded["conv"])
    strides = tuple(s for _, _, s in cfg.layer_specs())
    x = jax.random.normal(KEY, (2, n_syms * cfg.n_os))
    got = engine(x)
    want = cnn_ref.cnn_eq(x, weights, strides)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=ULP_TOL)


@pytest.mark.parametrize("tile_m", [8, 17, 64, 1024])        # boundary cases:
# partial last tile, non-power-of-two, single tile covering the stream
def test_fused_fp32_tile_boundaries(tile_m):
    cfg = LP.CNN
    engine, folded = _engine(cfg, "fused_fp32", tile_m=tile_m)
    weights = tuple((l["w"], l["b"]) for l in folded["conv"])
    strides = tuple(s for _, _, s in cfg.layer_specs())
    x = jax.random.normal(KEY, (1, 999 * cfg.n_os))          # odd stream
    np.testing.assert_allclose(np.asarray(engine(x)),
                               np.asarray(cnn_ref.cnn_eq(x, weights, strides)),
                               rtol=0, atol=ULP_TOL)


def test_engine_handles_unbatched_input():
    engine, _ = _engine(eq.CNNEqConfig(), "fused_fp32")
    x = jax.random.normal(KEY, (512 * 2,))
    y = engine(x)
    assert y.shape == (512,)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(engine(x[None])[0]))


# ---------------------------------------------------------------------------
# fused_int8 vs QAT fake-quant reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg,name", [(HT.CNN, "ht"), (LP.CNN, "lp")])
def test_fused_int8_matches_fake_quant(cfg, name):
    formats = tuple(INT8_FMT for _ in range(cfg.layers))
    engine, folded = _engine(cfg, "fused_int8", tile_m=32, formats=formats)
    weights = tuple((l["w"], l["b"]) for l in folded["conv"])
    strides = tuple(s for _, _, s in cfg.layer_specs())
    x = jax.random.normal(KEY, (2, 1024 * cfg.n_os))
    got = engine(x)
    want = cnn_ref.cnn_eq_quant(x, weights, strides, formats)
    lsb = 2.0 ** -(INT8_FMT[1] + INT8_FMT[3])    # accumulation grid LSB
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=lsb)


def test_fused_int8_quant_error_is_bounded():
    """int8 output differs from fp32 only by quantization noise, not junk."""
    cfg = eq.CNNEqConfig()
    formats = tuple(INT8_FMT for _ in range(cfg.layers))
    e8, folded = _engine(cfg, "fused_int8", formats=formats)
    e32 = EqualizerEngine.from_folded(folded, cfg, backend="fused_fp32",
                                      tile_m=64)
    x = jax.random.normal(KEY, (1, 2048))
    err = float(jnp.max(jnp.abs(e8(x) - e32(x))))
    assert 0 < err < 1.0         # quantized but sane (Q3.4 activation grid)


def test_int8_rejects_wide_formats():
    cfg = eq.CNNEqConfig()
    wide = tuple((4, 9, 3, 4) for _ in range(cfg.layers))    # 14-bit weights
    with pytest.raises(ValueError, match="int8"):
        _engine(cfg, "fused_int8", formats=wide)


def test_int8_kernel_rejects_wide_activation_formats():
    """Direct kernel API: 9-bit activations would WRAP in the int8 requant
    cast — must raise, not corrupt silently."""
    from repro.kernels.cnn_eq.cnn_eq import (cnn_eq_fused_int8,
                                             quantize_weights_int8)
    cfg = eq.CNNEqConfig()
    _, folded = _engine(cfg, "ref")
    weights = tuple((l["w"], l["b"]) for l in folded["conv"])
    strides = tuple(s for _, _, s in cfg.layer_specs())
    bad = tuple((2, 5, 4, 4) for _ in range(cfg.layers))     # 9-bit acts
    qw = quantize_weights_int8(weights, bad)                 # weights OK
    x = jax.random.normal(KEY, (1, 256))
    with pytest.raises(ValueError, match="wrap"):
        cnn_eq_fused_int8(x, qw, strides, bad, tile_m=16, interpret=True)


# ---------------------------------------------------------------------------
# fused_bf16 vs bf16 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    HT.CNN,
    eq.CNNEqConfig(layers=4, kernel=15, channels=4, v_parallel=4),
])
def test_fused_bf16_matches_oracle(cfg):
    """bf16 products are exact in fp32 and kernel and oracle add them in
    the same order → bitwise."""
    engine, folded = _engine(cfg, "fused_bf16", tile_m=16)
    weights = tuple((l["w"], l["b"]) for l in folded["conv"])
    strides = tuple(s for _, _, s in cfg.layer_specs())
    x = jax.random.normal(KEY, (2, 1021 * cfg.n_os))         # odd length
    got = engine(x)
    want = cnn_ref.cnn_eq_bf16(x, weights, strides)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_bf16_error_is_bounded():
    """bf16 differs from fp32 only by mantissa-rounding noise, not junk."""
    cfg = eq.CNNEqConfig()
    eb, folded = _engine(cfg, "fused_bf16")
    e32 = EqualizerEngine.from_folded(folded, cfg, backend="fused_fp32",
                                      tile_m=64)
    x = jax.random.normal(KEY, (1, 2048))
    err = float(jnp.max(jnp.abs(eb(x) - e32(x))))
    assert 0 < err < 0.2         # ~2^-8 relative at O(1) activations


# ---------------------------------------------------------------------------
# stacked multi-tenant launch (serving path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_engine_fn_matches_individual(backend):
    """One batched launch with per-row weights ≡ each engine run alone —
    the bitwise contract the serve micro-batcher relies on."""
    from repro.core.engine import stacked_engine_fn
    cfg = eq.CNNEqConfig()
    formats = (tuple(INT8_FMT for _ in range(cfg.layers))
               if backend == "fused_int8" else None)
    engines = [_engine(cfg, backend, tile_m=32, key=jax.random.PRNGKey(i),
                       formats=formats)[0]
               for i in range(3)]
    fn = stacked_engine_fn(engines)
    x = jax.random.normal(KEY, (3, 512 * cfg.n_os))
    y = np.asarray(fn(x))
    for i, e in enumerate(engines):
        np.testing.assert_array_equal(y[i:i + 1],
                                      np.asarray(e(x[i:i + 1])))


def test_stacked_engine_fn_rejects_mixed_groups():
    from repro.core.engine import stacked_engine_fn
    cfg = eq.CNNEqConfig()
    e_a, _ = _engine(cfg, "fused_fp32", tile_m=32)
    e_b, _ = _engine(cfg, "fused_fp32", tile_m=64)          # different tile
    with pytest.raises(ValueError, match="not batch-compatible"):
        stacked_engine_fn([e_a, e_b])


# ---------------------------------------------------------------------------
# backend selection / deployment
# ---------------------------------------------------------------------------

def _qat_params(cfg, wi, wf, ai, af):
    params = eq.init(KEY, cfg)
    params["qat"] = {
        f"layer{i}": {"w_int": jnp.asarray(float(wi)),
                      "w_frac": jnp.asarray(float(wf)),
                      "a_int": jnp.asarray(float(ai)),
                      "a_frac": jnp.asarray(float(af))}
        for i in range(cfg.layers)}
    return params


def test_auto_backend_selection():
    cfg = eq.CNNEqConfig()
    bn = eq.init_bn_state(cfg)
    # no QAT → fp32
    plain = eq.init(KEY, cfg)
    assert EqualizerEngine.from_params(plain, bn, cfg).backend == "fused_fp32"
    # learned 8-bit formats → int8
    p8 = _qat_params(cfg, 2, 5, 3, 4)
    assert EqualizerEngine.from_params(p8, bn, cfg).backend == "fused_int8"
    # 9–16-bit learned formats → native bf16 deployment
    p16 = _qat_params(cfg, 4, 9, 4, 9)
    assert EqualizerEngine.from_params(p16, bn, cfg).backend == "fused_bf16"
    # wider than 16 bits → fp32
    p32 = _qat_params(cfg, 8, 12, 8, 12)
    assert EqualizerEngine.from_params(p32, bn, cfg).backend == "fused_fp32"
    # explicit request still honoured
    assert EqualizerEngine.from_params(p8, bn, cfg,
                                       backend="ref").backend == "ref"
    with pytest.raises(ValueError, match="unknown backend"):
        EqualizerEngine.from_params(plain, bn, cfg, backend="fused_int4")


def test_auto_backend_falls_back_when_folding_overflows_grid():
    """QAT learns Q(w_int) on UNfolded weights; trained BN stats with tiny
    running variance scale the folded weights past the learned grid. The
    engine must refuse silent int8 saturation — it deploys bf16 instead
    (the exponent covers the overflowed range, no clipping)."""
    cfg = eq.CNNEqConfig()
    params = _qat_params(cfg, 2, 5, 3, 4)
    bn = eq.init_bn_state(cfg)
    # var = 1e-4 → fold gain g ≈ 100× → |w·g| ≫ 2^2
    bn = {"bn": [{"mean": s["mean"], "var": 1e-4 * jnp.ones_like(s["var"])}
                 for s in bn["bn"]]}
    engine = EqualizerEngine.from_params(params, bn, cfg)
    assert engine.backend == "fused_bf16"
    # benign BN stats keep the int8 deployment
    assert EqualizerEngine.from_params(params, eq.init_bn_state(cfg),
                                       cfg).backend == "fused_int8"
    # EXPLICIT int8 under the same overflow must refuse, not saturate
    with pytest.raises(ValueError, match="saturate"):
        EqualizerEngine.from_params(params, bn, cfg, backend="fused_int8")


def test_from_params_int8_matches_fake_quant_apply():
    """End-to-end deployment: trained-style params with frozen QAT widths →
    auto int8 engine ≡ the training-graph fake-quant forward (interior)."""
    cfg = eq.CNNEqConfig()
    bn = eq.init_bn_state(cfg)
    params = _qat_params(cfg, 2, 5, 3, 4)
    engine = EqualizerEngine.from_params(params, bn, cfg, tile_m=64)
    assert engine.backend == "fused_int8"
    x = jax.random.normal(KEY, (1, 1024 * cfg.n_os))
    got = engine(x)
    want, _ = eq.apply(params, x, cfg, train=False, bn_state=bn,
                       qat_enabled=True)
    o = cfg.receptive_field_syms
    # stream vs SAME padding differ only inside the overlap region. The
    # BN-fold ε (w → w/√(1+1e-5)) can flip individual rounding decisions
    # between Q(w)·g (training graph) and Q(w·g) (deployment), so allow 2
    # activation LSBs (observed max ≈ 1.1 LSB).
    np.testing.assert_allclose(np.asarray(got)[:, o:-o],
                               np.asarray(want)[:, o:-o], rtol=0,
                               atol=2.0 * 2.0 ** -4)


# ---------------------------------------------------------------------------
# backend equivalence through the partitioned stream path
# ---------------------------------------------------------------------------

def test_backend_equivalence_through_partitioned_apply():
    cfg = HT.CNN
    n_inst = 8
    formats = tuple(INT8_FMT for _ in range(cfg.layers))
    engines = {}
    _, folded = _engine(cfg, "ref")
    for backend in BACKENDS:
        engines[backend] = EqualizerEngine.from_folded(
            folded, cfg, backend=backend, tile_m=32,
            formats=formats if backend == "fused_int8" else None)
    x = jax.random.normal(KEY, (1024 * n_inst * cfg.n_os,))
    merged = {b: np.asarray(sp.partitioned_apply(e, x, n_inst, cfg))
              for b, e in engines.items()}
    # fp32 backends agree everywhere on the merged stream
    np.testing.assert_allclose(merged["ref"], merged["fused_fp32"],
                               rtol=0, atol=ULP_TOL)
    # every backend: partitioned == unsplit (the §6.1 overlap guarantee) —
    # int8 exactly (integer datapath), fp32 to fusion noise
    for b, e in engines.items():
        whole = np.asarray(e(x))
        tol = 0.0 if b == "fused_int8" else ULP_TOL
        np.testing.assert_allclose(merged[b], whole, rtol=0, atol=tol)


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "CACHE_PATH",
                        tmp_path / "autotune_tile_m.json")
    autotune.clear_cache()
    cfg = eq.CNNEqConfig()
    calls = []

    def make_fn(tile_m):
        engine, _ = _engine(cfg, "fused_fp32", tile_m=tile_m)
        calls.append(tile_m)
        return engine

    best = autotune.best_tile_m(cfg, "fused_fp32", make_fn,
                                candidates=(16, 64), probe_syms=512)
    assert best in (16, 64) and sorted(set(calls)) == [16, 64]
    # second query: memory cache, no new sweeps
    n = len(calls)
    assert autotune.best_tile_m(cfg, "fused_fp32", make_fn) == best
    assert len(calls) == n
    # cold process simulation: memory cleared, disk hit survives
    autotune.clear_cache()
    assert autotune.best_tile_m(cfg, "fused_fp32", make_fn) == best
    assert len(calls) == n
    # different backend → different cache slot
    assert autotune.cache_key(cfg, "fused_int8") != autotune.cache_key(
        cfg, "fused_fp32")


def test_engine_auto_tile_resolves(tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "CACHE_PATH",
                        tmp_path / "autotune_tile_m.json")
    autotune.clear_cache()
    monkeypatch.setattr(autotune, "DEFAULT_TILES", (16, 64))
    engine, _ = _engine(eq.CNNEqConfig(), "fused_fp32", tile_m="auto")
    t = engine.resolved_tile_m()
    assert t in (16, 64)
    assert engine.tile_m == t            # sticky after first resolution
    y = engine(jax.random.normal(KEY, (1, 1024)))
    assert y.shape == (1, 512)
