"""Quickstart: train the paper's CNN equalizer on the simulated 40 GBd
IM/DD optical channel and compare it with a linear FIR at the SAME
complexity (paper Fig. 2's headline comparison), then run the deployment
path (BN folded, fused Pallas kernel in interpret mode).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.channels import imdd
from repro.compile_cache import enable_compile_cache
from repro.core.equalizer import CNNEqConfig
from repro.core.fir import FIRConfig
from repro.core.train_eq import EqTrainConfig, train_equalizer
from repro.data.equalizer_data import channel_fn
from repro.kernels.cnn_eq import ops as cnn_ops


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    fn = channel_fn("imdd", imdd.IMDDConfig())
    tcfg = EqTrainConfig(steps=600, batch=8, seq_syms=256, lr=3e-3,
                         eval_syms=1 << 14)

    print("training the paper's CNN (V_p=8, L=3, K=9, C=5) …")
    cnn_cfg = CNNEqConfig()
    params, bn, cnn = train_equalizer(key, "cnn", cnn_cfg, fn, tcfg)
    print(f"  CNN  ({cnn_cfg.mac_per_symbol():.1f} MAC/sym): "
          f"BER {cnn['ber']:.3e}")

    print("training a same-complexity linear FIR …")
    _, _, fir = train_equalizer(key, "fir", FIRConfig(taps=57), fn, tcfg)
    print(f"  FIR  (57.0 MAC/sym): BER {fir['ber']:.3e}")

    # deployment path: fold BN and run the fused Pallas kernel
    rx, syms = imdd.simulate(key, imdd.IMDDConfig(), 4096)
    y = cnn_ops.equalize(params, bn, rx, cnn_cfg, use_pallas=True)
    from repro.channels.common import ber_from_soft
    print(f"fused-kernel deployment BER on a fresh frame: "
          f"{float(ber_from_soft(y, syms, 2)):.3e}")
    print("done — see benchmarks/ for the full paper-figure reproductions.")


if __name__ == "__main__":
    main()
