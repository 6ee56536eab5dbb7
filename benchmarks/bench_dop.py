"""Fig. 8 — the flexible degree-of-parallelism (DOP) study, TPU-adapted.

FPGA: DOP ∈ {1,5,10,25,225} scales MACs/cycle (resources & power follow).
TPU analogue: the kernel's tile shape sets how much of the 128×128 MXU and
the 8×128 VPU lanes each step engages — our DOP = effective lane
utilization. We sweep the fused-kernel tile width and report (a) the
roofline-projected throughput per tile shape and (b) the measured interpret-
mode-independent arithmetic utilization, reproducing the paper's
throughput-vs-parallelism trade-off on the new hardware axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import equalizer_lp as LP
from repro.core import autotune
from repro.core import equalizer as eq
from repro.core.engine import EqualizerEngine
from repro.launch import roofline as rl

from .common import Bench


def tile_utilization(cfg, tile_m: int) -> dict:
    """Static MXU/VPU utilization of the fused kernel at tile width tile_m.

    Each tap contributes a (C_out × C_in)·(C_in × tile) matmul: the MXU
    processes it in ⌈C_out/8⌉ × ⌈C_in/128⌉ … passes; with C ≤ 5 the systolic
    array is PADDING-dominated — the TPU's "DOP" comes from the tile (width)
    dimension instead, which fills the 128-lane axis.
    """
    c = cfg.channels
    lanes = 128
    sublanes = 8
    # fraction of MXU columns doing useful work per tap-matmul
    width_fill = min(tile_m, lanes) / lanes
    ch_fill = (c / sublanes) if c < sublanes else 1.0
    dop_equiv = width_fill * ch_fill * lanes * sublanes
    macs_per_sym = cfg.mac_per_symbol()
    flops_per_sym = 2 * macs_per_sym
    v5e = rl.peaks(rl.V5E)
    eff_flops = v5e["bf16_flops"] * width_fill * ch_fill
    t_comp = flops_per_sym / eff_flops
    bytes_per_sym = (cfg.n_os + 1) * 2.0
    t_mem = bytes_per_sym / v5e["hbm_bytes_per_s"]
    rate = 1.0 / max(t_comp, t_mem)
    return {"tile_m": tile_m, "lane_fill": width_fill, "chan_fill": ch_fill,
            "dop_equivalent_macs": dop_equiv,
            "throughput_gsyms": rate / 1e9,
            "bound": "compute" if t_comp > t_mem else "memory"}


def measured_tile_sweep(cfg, tiles=(16, 32, 64, 128, 256),
                        n_syms: int = 1 << 14, iters: int = 3) -> list[dict]:
    """MEASURED engine throughput per tile_m — the DOP knob on real silicon
    (interpret mode on CPU; the same sweep the autotuner caches)."""
    params = eq.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n_syms * cfg.n_os))
    rows = []
    for tile_m in tiles:
        engine = EqualizerEngine.from_params(params, eq.init_bn_state(cfg),
                                             cfg, backend="fused_fp32",
                                             tile_m=tile_m)
        dt = autotune.time_callable(engine, x, iters=iters)
        rows.append({"tile_m": tile_m, "syms_per_s": n_syms / dt})
    return rows


def run() -> dict:
    bench = Bench("dop_flexibility", "Fig. 8 / §5.2")
    cfg = LP.CNN
    rows = [tile_utilization(cfg, t) for t in (1, 8, 32, 128, 512)]
    bench.record("tpu_tile_sweep", rows)
    measured = measured_tile_sweep(cfg)
    bench.record("measured_engine_tile_sweep", measured)
    best = autotune.best_tile_m(
        cfg, "fused_fp32",
        lambda t: EqualizerEngine.from_params(
            eq.init(jax.random.PRNGKey(0), cfg), eq.init_bn_state(cfg), cfg,
            backend="fused_fp32", tile_m=t))
    bench.record("autotuned_tile_m", best)
    # FPGA reference trade-off (paper Fig. 8b): DOP ↑ ⇒ throughput ↑, power ↑
    fpga = [{"dop": d,
             "throughput_mbps": 4.0 + (110.0 - 4.0) * (d - 1) / (225 - 1),
             "power_w": 0.1 + (0.2 - 0.1) * (d - 1) / (225 - 1)}
            for d in LP.DOPS]
    bench.record("fpga_reference_tradeoff", fpga)
    mono = all(a["throughput_gsyms"] <= b["throughput_gsyms"] + 1e-9
               for a, b in zip(rows, rows[1:]))
    bench.record("throughput_monotone_in_dop", bool(mono))
    print("[bench_dop] tile sweep:",
          [(r["tile_m"], round(r["throughput_gsyms"], 1), r["bound"])
           for r in rows])
    print("[bench_dop] measured engine sweep:",
          [(r["tile_m"], f"{r['syms_per_s']:.3g}") for r in measured],
          f"autotuned tile_m={best}")
    return bench.finish()


if __name__ == "__main__":
    run()
