"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes, plus equivalence to the core (training-time)
modules on the stream interior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import equalizer as eq
from repro.core import qat as qat_lib
from repro.core import volterra as vol_core
from repro.kernels.cnn_eq import ops as cnn_ops
from repro.kernels.cnn_eq import ref as cnn_ref
from repro.kernels.cnn_eq.cnn_eq import cnn_eq_fused
from repro.kernels.conv1d import ref as c1_ref
from repro.kernels.conv1d.conv1d import conv1d as conv1d_pallas
from repro.kernels.quant import ops as q_ops
from repro.kernels.volterra import ops as v_ops

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# conv1d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,c_in,c_out,width,kernel,stride", [
    (1, 1, 5, 128, 9, 8),          # equalizer layer 1
    (2, 5, 5, 256, 9, 1),          # mid layer
    (2, 5, 8, 254, 9, 2),          # output layer, non-tile-aligned width
    (1, 3, 7, 64, 15, 4),
    (4, 2, 2, 33, 3, 1),           # tiny odd width
    (1, 1, 1, 512, 21, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_vs_ref(batch, c_in, c_out, width, kernel, stride, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (batch, c_in, width), dtype)
    w = jax.random.normal(k2, (c_out, c_in, kernel), dtype) * 0.3
    b = jax.random.normal(k3, (c_out,), dtype)
    got = conv1d_pallas(x, w, b, stride, tile_w=64, interpret=True)
    want = c1_ref.conv1d(x, w, b, stride)
    assert got.shape == want.shape
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_conv1d_tile_sweep():
    """Result must be invariant to the BlockSpec tile choice (the DOP knob)."""
    x = jax.random.normal(KEY, (2, 5, 300), jnp.float32)
    w = jax.random.normal(KEY, (5, 5, 9), jnp.float32) * 0.2
    b = jnp.zeros((5,))
    ref = c1_ref.conv1d(x, w, b, 1)
    for tile in (8, 32, 128, 512):
        got = conv1d_pallas(x, w, b, 1, tile_w=tile, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused CNN equalizer
# ---------------------------------------------------------------------------

def _folded(cfg, key=KEY):
    params = eq.init(key, cfg)
    bn = eq.init_bn_state(cfg)
    # randomize BN state so folding is non-trivial
    bn = {"bn": [{"mean": 0.1 * jax.random.normal(key, s["mean"].shape),
                  "var": 1.0 + 0.5 * jax.random.uniform(key, s["var"].shape)}
                 for s in bn["bn"]]}
    return params, bn, eq.fold_bn(params, bn, cfg)


@pytest.mark.parametrize("cfg", [
    eq.CNNEqConfig(),                                       # paper operating pt
    eq.CNNEqConfig(layers=4, kernel=15, channels=4, v_parallel=4),
    eq.CNNEqConfig(layers=3, kernel=21, channels=3, v_parallel=2),
    eq.CNNEqConfig(layers=5, kernel=9, channels=5, v_parallel=16),
])
def test_cnn_eq_fused_vs_ref(cfg):
    _, _, folded = _folded(cfg)
    weights = cnn_ops.weights_of(folded)
    strides = cnn_ops.strides_of(cfg)
    x = jax.random.normal(KEY, (2, 64 * cfg.v_parallel * cfg.n_os))
    got = cnn_eq_fused(x, weights, strides, tile_m=16, interpret=True)
    want = cnn_ref.cnn_eq(x, weights, strides)
    assert got.shape == want.shape == (2, x.shape[1] // cfg.n_os)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_cnn_eq_fused_matches_core_on_interior():
    """Kernel (stream semantics) == core apply_folded (SAME padding) away
    from the edges — the overlap region the paper's OGM/ORM discards."""
    cfg = eq.CNNEqConfig()
    params, bn, folded = _folded(cfg)
    x = jax.random.normal(KEY, (1, 2048 * cfg.n_os))
    y_kernel = cnn_ops.equalize(params, bn, x, cfg, use_pallas=True,
                                tile_m=32)
    y_core = eq.apply_folded(folded, x, cfg)
    o = cfg.receptive_field_syms
    np.testing.assert_allclose(np.asarray(y_kernel)[:, o:-o],
                               np.asarray(y_core)[:, o:-o],
                               rtol=2e-4, atol=2e-4)


def test_cnn_eq_tile_invariance():
    cfg = eq.CNNEqConfig()
    _, _, folded = _folded(cfg)
    weights = cnn_ops.weights_of(folded)
    strides = cnn_ops.strides_of(cfg)
    x = jax.random.normal(KEY, (1, 4096))
    outs = [cnn_eq_fused(x, weights, strides, tile_m=t, interpret=True)
            for t in (8, 64, 256)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-4, atol=1e-4)


def test_cnn_eq_rejects_nonpositive_tile():
    """A tile the kernel cannot use raises instead of being resized."""
    cfg = eq.CNNEqConfig()
    _, _, folded = _folded(cfg)
    with pytest.raises(ValueError, match="tile_m"):
        cnn_eq_fused(jnp.zeros((1, 256)), cnn_ops.weights_of(folded),
                     cnn_ops.strides_of(cfg), tile_m=0, interpret=True)


# ---------------------------------------------------------------------------
# quantization kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128,), (5, 64), (3, 5, 33)])
@pytest.mark.parametrize("ib,fb", [(2.0, 6.0), (4.0, 9.0), (1.0, 1.0)])
def test_quant_vs_ref(shape, ib, fb):
    x = 8.0 * jax.random.normal(KEY, shape)
    got = q_ops.quantize_pallas(x, jnp.asarray(ib), jnp.asarray(fb),
                                interpret=True)
    want = q_ops.quantize_ref(x, jnp.asarray(ib), jnp.asarray(fb))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)
    core = qat_lib.quantize_fixed(x, jnp.asarray(ib), jnp.asarray(fb))
    np.testing.assert_allclose(np.asarray(got), np.asarray(core),
                               rtol=0, atol=1e-7)


# ---------------------------------------------------------------------------
# volterra kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m1,m2,m3", [(25, 9, 0), (9, 3, 3), (15, 0, 0),
                                      (41, 15, 9)])
def test_volterra_vs_ref(m1, m2, m3):
    cfg = vol_core.VolterraConfig(m1=m1, m2=m2, m3=m3)
    params = vol_core.init(KEY, cfg)
    # make the nonlinear kernels non-trivial
    if "w2" in params:
        params["w2"] = 0.1 * jax.random.normal(KEY, params["w2"].shape)
    if "w3" in params:
        params["w3"] = 0.05 * jax.random.normal(KEY, params["w3"].shape)
    x = jax.random.normal(KEY, (2, 256))
    got = v_ops.equalize(params, x, cfg, use_pallas=True, tile=32)
    want = v_ops.equalize(params, x, cfg, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_volterra_matches_core_on_interior():
    cfg = vol_core.VolterraConfig(m1=9, m2=5, m3=0)
    params = vol_core.init(KEY, cfg)
    params["w2"] = 0.1 * jax.random.normal(KEY, (5, 5))
    x = jax.random.normal(KEY, (1, 512))
    y_k = v_ops.equalize(params, x, cfg, use_pallas=True)
    y_c = vol_core.apply(params, x, cfg)
    o = max(cfg.m1, cfg.m2) // 2 + 1
    np.testing.assert_allclose(np.asarray(y_k)[:, o:-o],
                               np.asarray(y_c)[:, o:-o], rtol=1e-4,
                               atol=1e-4)
