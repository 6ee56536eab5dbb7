"""The work a symbol needs: operations, and the least bytes any
implementation of the backend's numerical contract must move.

Counted from the topology in a configuration file (`bench/configs`), not
from the program: the layer stack of the paper's CNN (arXiv 2405.02323
§3.1) as it is run, on a waveform of N_os samples per symbol:

    layer 1: 1 -> C channels, K taps, stride V_p over the samples
    layer i: C -> C channels, K taps, stride 1        (i = 2 .. L-1)
    layer L: C -> V_p channels, K taps, stride N_os   (V_p symbols out)

A layer whose outputs lie every s samples of the input has N_os / s
output positions per symbol, and each position costs c_in * c_out * K
multiply-adds. Recompute (overlap between instances, padding to launch
buckets, tile halos) is not useful work and is not counted.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# bytes of one input sample that the backend's contract lets travel:
#   fused_int8 — the layer-0 requantization puts every sample on the int8
#     grid Q(a_int).(a_frac) before any arithmetic, so one byte per sample
#     is lossless (the wire's int8 frames rely on the same fact);
#   fused_bf16 — the kernel rounds every sample to bfloat16 first, so two
#     bytes per sample lose nothing.
INPUT_BYTES = {"fused_int8": 1, "fused_bf16": 2}
# soft symbols leave the kernel as float32 for the slicer and the host
OUTPUT_BYTES = 4
# weight bytes per element on the backend's datapath (biases stay fp32)
WEIGHT_BYTES = {"fused_int8": 1, "fused_bf16": 2}


def layers(topo: Dict) -> List[Tuple[int, int, int, int]]:
    """[(c_in, c_out, K, stride), ...] of the topology."""
    c, k, vp, nos = (topo["channels"], topo["kernel"], topo["v_parallel"],
                     topo["n_os"])
    out = [(1, c, k, vp)]
    out += [(c, c, k, 1)] * (topo["layers"] - 2)
    out.append((c, vp, k, nos))
    return out


def macs_per_symbol(topo: Dict) -> float:
    """Multiply-adds per output symbol of one network run."""
    total, cum = 0.0, 1
    for c_in, c_out, k, stride in layers(topo):
        cum *= stride                        # samples per output position
        total += c_in * c_out * k * topo["n_os"] / cum
    return total


def flop_per_symbol(topo: Dict) -> float:
    """Operations per symbol (a multiply-add is two)."""
    return 2.0 * macs_per_symbol(topo)


def least_bytes_per_symbol(topo: Dict, backend: str) -> float:
    """Input samples at the contract's narrowest lossless width, plus the
    fp32 symbol out. Weights are counted per launch row, apart."""
    return topo["n_os"] * INPUT_BYTES[backend] + OUTPUT_BYTES


def weight_bytes_per_row(topo: Dict, backend: str) -> float:
    """One tenant's weights and fp32 biases, read at least once per launch
    row that carries them."""
    w = sum(c_in * c_out * k for c_in, c_out, k, _ in layers(topo))
    b = sum(c_out for _, c_out, _, _ in layers(topo))
    return w * WEIGHT_BYTES[backend] + 4 * b


def least_time_s(topo: Dict, backend: str, n_symbols: float,
                 launch_rows: float, compute_peak: float,
                 hbm_bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take for the work, and which bound
    (`compute` or `memory`) sets it."""
    t_compute = n_symbols * flop_per_symbol(topo) / compute_peak
    n_bytes = (n_symbols * least_bytes_per_symbol(topo, backend)
               + launch_rows * weight_bytes_per_row(topo, backend))
    t_memory = n_bytes / hbm_bytes_per_s
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
