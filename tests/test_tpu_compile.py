"""Compile-only checks of the fused kernels for a described TPU v5e.

Interpret mode on the CPU accepts layouts the TPU compiler refuses (strided
lane slices, unaligned blocks). These tests lower and compile the main path
at deployment widths for a `v5e:2x2` topology that is described, not
attached — nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and test workers import
every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import equalizer_ht as HT
from repro.core import equalizer as eq
from repro.core import stream_partition as sp
from repro.core.engine import EqualizerEngine
from repro.kernels.cnn_eq import (cast_weights_bf16, cnn_eq_fused,
                                  cnn_eq_fused_bf16, cnn_eq_fused_int8,
                                  quantize_weights_int8)
from repro.kernels.cnn_eq.cnn_eq import table_rows
from repro.parallel import halo

CFG = HT.CNN
STRIDES = eq.layer_strides(CFG)
INT8_FMT = tuple((2, 5, 3, 4) for _ in range(CFG.layers))
HT_SHAPE = (HT.N_INSTANCES, HT.L_INST * CFG.n_os)      # 64 × 14,640 samples
BACKENDS = ("fused_fp32", "fused_bf16", "fused_int8")


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "absent"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _weights(key, backend, batch=None):
    """Folded HT weights in the backend's deployment form; when batch is
    given, a weight table of `batch` sets in the kernel's operand layout
    (`table_rows`)."""
    keys = [key] if batch is None else list(jax.random.split(key, batch))
    sets = []
    for k in keys:
        w = eq.folded_weights(eq.fold_bn(eq.init(k, CFG),
                                         eq.init_bn_state(CFG), CFG))
        if backend == "fused_bf16":
            w = cast_weights_bf16(w)
        elif backend == "fused_int8":
            w = quantize_weights_int8(w, INT8_FMT)
        sets.append(w)
    if batch is None:
        return sets[0]
    rows = [table_rows(s, backend) for s in sets]
    return jax.tree.map(lambda *r: jnp.stack(r), *rows)


def _kernel_fn(backend, weights, tile_m, slots=None):
    kw = dict(tile_m=tile_m, interpret=False, slots=slots)
    if backend == "fused_fp32":
        return lambda x: cnn_eq_fused(x, weights, STRIDES, **kw)
    if backend == "fused_bf16":
        return lambda x: cnn_eq_fused_bf16(x, weights, STRIDES, **kw)
    return lambda x: cnn_eq_fused_int8(x, weights, STRIDES, INT8_FMT, **kw)


def _compile_has_kernel(fn, arg):
    text = jax.jit(fn).lower(arg).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_compiles_at_ht_deployment_shape(backend, one_chip):
    """Shared weights, 64 instances × 7,320 symbols in one batched call."""
    w = _weights(jax.random.PRNGKey(0), backend)
    x = jax.ShapeDtypeStruct(HT_SHAPE, jnp.float32, sharding=one_chip)
    _compile_has_kernel(_kernel_fn(backend, w, tile_m=128), x)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_compiles_stacked_serving_shape(backend, one_chip):
    """Per-row tenant weights (the serving launch): 8 rows at the tile
    width serving uses, each selecting its entry of a 64-set weight table
    through the scalar-prefetched slot vector."""
    table = _weights(jax.random.PRNGKey(1), backend, batch=64)
    slots = np.array([5, 63, 0, 5, 17, 2, 40, 1], np.int32)
    x = jax.ShapeDtypeStruct((8, 1024 * CFG.n_os), jnp.float32,
                             sharding=one_chip)
    _compile_has_kernel(_kernel_fn(backend, table, tile_m=16, slots=slots),
                        x)


def test_halo_apply_batched_compiles_on_four_chips(topo):
    """The N_i-instances path: halo `ppermute`s over a 4-chip mesh around
    the int8 fused kernel."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    engine = EqualizerEngine.from_folded(
        eq.fold_bn(eq.init(jax.random.PRNGKey(3), CFG),
                   eq.init_bn_state(CFG), CFG),
        CFG, backend="fused_int8", formats=INT8_FMT, tile_m=128,
        interpret=False)
    x = jax.ShapeDtypeStruct(
        (8, HT.L_INST * CFG.n_os * 4), jnp.float32,
        sharding=NamedSharding(mesh, P(None, "data")))
    text = _compile_has_kernel(
        lambda v: halo.halo_apply_batched(engine, v, CFG, mesh), x)
    assert "collective-permute" in text


def test_partitioned_ht_unit_names_its_scopes_in_the_compiled_program(
        one_chip):
    """The device profile attributes op time by op name metadata: the
    partition split keeps its scope through fusion, and the kernel its
    per-datapath name."""
    import re
    engine = EqualizerEngine.from_folded(
        eq.fold_bn(eq.init(jax.random.PRNGKey(4), CFG),
                   eq.init_bn_state(CFG), CFG),
        CFG, backend="fused_int8", formats=INT8_FMT, tile_m=128,
        interpret=False)
    x = jax.ShapeDtypeStruct((HT.N_INSTANCES * HT.L_INST * CFG.n_os,),
                             jnp.float32, sharding=one_chip)
    text = _compile_has_kernel(
        lambda v: sp.partitioned_apply(engine, v, HT.N_INSTANCES, CFG), x)
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/partition/" in n for n in op_names)
    # the split is static slices: no instruction under `partition/`,
    # fused or not, is a gather
    assert not [ln for ln in text.splitlines()
                if "/partition/" in ln and re.search(r"\bgather\(", ln)]
    assert any("/tile_windows/" in n for n in op_names)
    assert any(n.endswith("/cnn_eq_fused_int8/pallas_call")
               for n in op_names)
