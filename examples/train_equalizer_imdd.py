"""End-to-end training driver for the equalizer with the full production
substrate: on-device channel simulation as the data pipeline, 3-phase
quantization-aware training, checkpointing + restart, and the DSE
complexity ceilings (FPGA vs TPU) deciding the operating point — the
paper's cross-layer flow in one script.

    PYTHONPATH=src python examples/train_equalizer_imdd.py [--steps 1200]
"""
import argparse

import jax

from repro.channels import imdd
from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.core import dse, qat as qat_lib
from repro.core.equalizer import CNNEqConfig
from repro.core.train_eq import EqTrainConfig, train_equalizer
from repro.data.equalizer_data import channel_fn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--qlf", type=float, default=5e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_eq_ckpt")
    args = ap.parse_args()

    key = jax.random.PRNGKey(1)
    fn = channel_fn("imdd", imdd.IMDDConfig())

    # --- cross-layer operating-point choice (paper §3.5 / DESIGN.md §2) ---
    fpga_ceiling = dse.mac_sym_max_fpga()
    tpu_ceiling = dse.mac_sym_max_tpu(chips=1)
    candidates = [CNNEqConfig(channels=c) for c in (5, 10, 16)]
    feasible_fpga = [c for c in candidates
                     if c.mac_per_symbol() <= fpga_ceiling]
    feasible_tpu = [c for c in candidates
                    if c.mac_per_symbol() <= tpu_ceiling]
    cfg = max(feasible_tpu, key=lambda c: c.mac_per_symbol())
    print(f"ceilings: FPGA {fpga_ceiling:.1f} MAC/sym "
          f"(admits C={max(c.channels for c in feasible_fpga)}), "
          f"TPU {tpu_ceiling:.0f} (admits C={cfg.channels}) → "
          f"training C={cfg.channels}")

    # --- 3-phase QAT training ---------------------------------------------
    qcfg = qat_lib.QATConfig(qlf=args.qlf, init_int_bits=8.0,
                             init_frac_bits=8.0)
    tcfg = EqTrainConfig(steps=args.steps, batch=8, seq_syms=256, lr=3e-3,
                         eval_syms=1 << 15)
    params, bn, info = train_equalizer(key, "cnn", cfg, fn, tcfg,
                                       qat_cfg=qcfg, record_every=100)
    print(f"BER {info['ber']:.3e} at {info['bits_params']:.1f}b weights / "
          f"{info['bits_acts']:.1f}b activations")
    for name, q in params["qat"].items():
        print(f"  {name}: deploys as {qat_lib.deployment_dtype(q)}")

    ckpt = CheckpointManager(args.ckpt_dir, keep_k=2)
    path = ckpt.save(args.steps, (params, bn), extra=dict(info, history=[]))
    print(f"checkpoint at {path}")


if __name__ == "__main__":
    main()
