"""Figs. 13/14/15 — platform comparison for the CNN equalizer.

Measured on THIS machine: the jitted JAX-CPU implementation across batch
sizes (the paper's CPU row). Projected from the roofline model: one TPU-v5e
chip running the fused Pallas equalizer (compute/memory terms from the
kernel's arithmetic; the §Roofline machinery), and the paper's reported
FPGA/GPU numbers carried as reference constants for the comparison table.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import equalizer_ht as HT
from repro.core import equalizer as eq
from repro.kernels.cnn_eq import ops as cnn_ops
from repro.launch import roofline as rl

from .common import Bench

# paper-reported reference points (Gbit/s at large batch; §7.3)
PAPER_REFS = {
    "fpga_ht_gbps": 40.0,              # > 40 GBd PAM2 ⇒ 40 Gbit/s
    "rtx2080ti_tensorrt_gbps": 12.0,
    "cpu_i9_gbps": 0.4,
    "fpga_vs_gpu_same_batch": 4500.0,
}


def tpu_projection(cfg) -> dict:
    """Roofline projection of the fused kernel on one v5e chip."""
    macs_per_sym = cfg.mac_per_symbol()
    flops_per_sym = 2.0 * macs_per_sym
    # bytes/sym: stream in (N_os samples bf16) + out (1 sym bf16); weights
    # stay in VMEM
    bytes_per_sym = (cfg.n_os + 1) * 2.0
    v5e = rl.peaks(rl.V5E)
    t_comp = flops_per_sym / v5e["bf16_flops"]
    t_mem = bytes_per_sym / v5e["hbm_bytes_per_s"]
    sym_rate = 1.0 / max(t_comp, t_mem)
    return {
        "sym_rate_gsyms": sym_rate / 1e9,
        "throughput_gbps_pam2": sym_rate / 1e9,
        "bound": "compute" if t_comp > t_mem else "memory",
        "mfu_at_bound": flops_per_sym * sym_rate / v5e["bf16_flops"],
    }


def run(batches=(1, 8, 64, 512), n_syms: int = 16384) -> dict:
    bench = Bench("platform_comparison", "Figs. 13/14/15 / §7.3")
    cfg = HT.CNN
    key = jax.random.PRNGKey(0)
    params = eq.init(key, cfg)
    bn = eq.init_bn_state(cfg)
    folded = eq.fold_bn(params, bn, cfg)
    weights = cnn_ops.weights_of(folded)
    strides = cnn_ops.strides_of(cfg)

    from repro.kernels.cnn_eq.ref import cnn_eq as ref_fn
    fn = jax.jit(lambda x: ref_fn(x, weights, strides))

    rows = []
    for b in batches:
        x = jax.random.normal(key, (b, n_syms * cfg.n_os))
        fn(x).block_until_ready()                      # compile + warm
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            fn(x).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        syms = b * n_syms
        rows.append({
            "batch": b, "syms_per_batch": n_syms,
            "throughput_gbps": syms / dt / 1e9,        # PAM2: 1 bit/sym
            "latency_ms": dt * 1e3,
        })
        print(f"[bench_platform] cpu-jax b={b}: "
              f"{rows[-1]['throughput_gbps']:.4f} Gbit/s, "
              f"{rows[-1]['latency_ms']:.1f} ms")
    bench.record("cpu_jax_measured", rows)

    proj = tpu_projection(cfg)
    proj["projected_instances_equivalent"] = (
        proj["sym_rate_gsyms"] * 1e9 / (HT.F_CLK * cfg.v_parallel))
    bench.record("tpu_v5e_projected_single_chip", proj)
    bench.record("paper_reference_points", PAPER_REFS)
    # the structural claim (Fig. 13): a platform whose architecture is
    # matched to the CNN (FPGA there, TPU-roofline here) beats the
    # general-purpose CPU by orders of magnitude
    cpu_best = max(r["throughput_gbps"] for r in rows)
    bench.record("tpu_over_cpu_ratio",
                 proj["throughput_gbps_pam2"] / max(cpu_best, 1e-9))
    print(f"[bench_platform] TPU-projected {proj['throughput_gbps_pam2']:.1f}"
          f" Gbit/s ({proj['bound']}-bound) vs CPU best {cpu_best:.3f}")
    return bench.finish()


if __name__ == "__main__":
    run()
