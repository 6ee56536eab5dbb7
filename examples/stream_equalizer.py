"""Multi-instance stream equalization — the paper's §5.3 hardware path:

    OGM (overlap) → SSM tree (split) → N_i × CNN → MSM (merge) → ORM

run two ways: (1) the pure-JAX reference (any machine), and (2) the
TPU-native halo-exchange shard_map with one CNN instance per device, over
every device the process sees (N_i = device count).

    PYTHONPATH=src python examples/stream_equalizer.py

On a CPU host, give JAX several virtual devices for the mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python examples/stream_equalizer.py
"""
import jax
import jax.numpy as jnp

from repro.channels import imdd
from repro.compile_cache import enable_compile_cache
from repro.core import equalizer as eq
from repro.core import seqlen_opt, stream_partition as sp
from repro.core import timing_model as tm
from repro.core.engine import EqualizerEngine
from repro.parallel import halo


def main() -> None:
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    cfg = eq.CNNEqConfig()
    params = eq.init(key, cfg)
    # the production inference path: BN-folded, fused Pallas kernel,
    # autotuned tiling ("auto" backend upgrades to int8 when QAT formats
    # are present in params)
    engine = EqualizerEngine.from_params(params, eq.init_bn_state(cfg), cfg,
                                         backend="auto", tile_m="auto")

    n_inst = len(jax.devices())
    n_syms = 1024 * n_inst
    rx, _ = imdd.simulate(key, imdd.IMDDConfig(), n_syms)

    y_single = engine(rx)
    y_ref = sp.partitioned_apply(engine, rx, n_inst, cfg)
    mesh = jax.make_mesh((n_inst,), ("data",))
    y_halo = halo.halo_apply(engine, rx, cfg, mesh)
    o = sp.overlap_symbols(cfg)
    err_ref = float(jnp.max(jnp.abs(y_ref[o:-o] - y_single[o:-o])))
    err_halo = float(jnp.max(jnp.abs(y_halo[o:-o] - y_single[o:-o])))
    print(f"{n_inst} instances over {n_inst} devices "
          f"(engine: {engine.describe()}):")
    print(f"  split-tree reference vs single instance (interior): "
          f"max err {err_ref:.2e}")
    print(f"  halo-exchange shard_map vs single instance (interior): "
          f"max err {err_halo:.2e}")

    hw = tm.fpga_profile(cfg)
    if tm.max_throughput(hw, n_inst) > 80e9:
        l_inst = seqlen_opt.optimal_l_inst(cfg, hw, n_inst, 80e9)
        print(f"  ℓ_inst for 80 GSa/s: {l_inst} "
              f"(λ = {tm.symbol_latency(cfg, hw, n_inst, l_inst)*1e6:.1f} µs)")


if __name__ == "__main__":
    main()
