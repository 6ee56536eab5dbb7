"""The plain reference: the paper's CNN equalizer on a sample stream, in
float64 numpy, written from the layer equations and not from the program.

Stream semantics (the FPGA's and the kernel's contract): the stream is
padded once with half a receptive field of zeros on the left and zeros on
the right, and the layers run VALID convolutions over it, so position p of
the last layer sees input samples [p·T − halo, p·T + halo] with T = V_p·N_os
samples per network pass. Layer i computes

    h_i[o, n] = Σ_c Σ_k w_i[o, c, k] · q(h_{i-1})[c, k + s_i·n] + b_i[o]

with ReLU between layers, where q is the datapath's rounding of a layer's
input operands:

  * "int8": weights on the grid Q(w_int).(w_frac), inputs snapped to
    Q(a_int).(a_frac) (round half to even, saturating), integer products and
    sums (exact in float64), then one float32 rounding of
    sum·2^-(w_frac+a_frac) + b. Exact: any implementation of the datapath
    gives the same float32 bits.
  * "bf16": weights and inputs rounded to bfloat16, products and sums
    carried in float64, each layer's output rounded to float32.

and two lower precisions that serve as controls, the step below each:
"int4" (the int8 formats cut to 4 bits by dropping fraction bits) and
"fp8" (operands rounded to float8 e4m3 instead of bfloat16).

The last layer's V_p channels at position n are symbols n·V_p … n·V_p+V_p-1.
The stream is equalized in blocks of positions, so that a long stream
fits in memory.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

from .work import layers

Format = Tuple[int, int, int, int]     # (w_int, w_frac, a_int, a_frac)
DATAPATHS = ("int8", "bf16", "int4", "fp8")
CONTROL = {"int8": "int4", "bf16": "fp8"}   # the precision one step below


def receptive_halo(topo: Dict) -> int:
    """Half a receptive field of the last layer, in input samples."""
    r, jump = 0, 1
    for _, _, k, s in layers(topo):
        r += (k // 2) * jump
        jump *= s
    return r


def total_stride(topo: Dict) -> int:
    n = 1
    for _, _, _, s in layers(topo):
        n *= s
    return n


def _input_span(topo: Dict, n_pos: int) -> int:
    """Input samples that n_pos consecutive last-layer positions read."""
    span = n_pos
    for _, _, k, s in reversed(layers(topo)):
        span = (span - 1) * s + k
    return span


def _grid(v: np.ndarray, i_bits: int, f_bits: int) -> np.ndarray:
    """Integer grid values of v on Q(i_bits).(f_bits), saturating."""
    hi = 2.0 ** (i_bits + f_bits) - 1.0
    lo = -(2.0 ** (i_bits + f_bits))
    return np.clip(np.round(v.astype(np.float64) * 2.0 ** f_bits), lo, hi)


def _narrow(fmt: Format, bits: int) -> Format:
    """The same integer widths with the fraction cut to fit `bits`."""
    wi, _, ai, _ = fmt
    return (wi, max(0, bits - 1 - wi), ai, max(0, bits - 1 - ai))


def _round_float(v: np.ndarray, dtype) -> np.ndarray:
    return np.asarray(v, np.float32).astype(dtype).astype(np.float64)


class Reference:
    """The equalizer of one configuration on one datapath.

    topo:     the topology of a configuration file;
    datapath: one of DATAPATHS;
    formats:  per-layer (w_int, w_frac, a_int, a_frac), for the integer
              datapaths.
    """

    def __init__(self, topo: Dict, datapath: str,
                 formats: Optional[Sequence[Format]] = None):
        if datapath not in DATAPATHS:
            raise ValueError(f"unknown datapath {datapath!r}")
        self.topo = topo
        self.datapath = datapath
        self.layers = layers(topo)
        if datapath in ("int8", "int4"):
            if formats is None or len(formats) != len(self.layers):
                raise ValueError("integer datapaths need one format a layer")
            bits = 8 if datapath == "int8" else 4
            self.formats = [tuple(int(v) for v in f) if bits == 8
                            else _narrow(tuple(int(v) for v in f), 4)
                            for f in formats]
        else:
            self.formats = None
        self.halo = receptive_halo(topo)
        self.ts = total_stride(topo)

    def _weights(self, weights) -> List[Tuple[np.ndarray, np.ndarray]]:
        out = []
        for i, (w, b) in enumerate(weights):
            w = np.asarray(w, np.float32)
            if self.formats is not None:
                wq = _grid(w, self.formats[i][0], self.formats[i][1])
            elif self.datapath == "bf16":
                wq = _round_float(w, ml_dtypes.bfloat16)
            else:
                wq = _round_float(w, ml_dtypes.float8_e4m3fn)
            out.append((wq, np.asarray(b, np.float64)))
        return out

    def _layer(self, i: int, h: np.ndarray, w: np.ndarray, b: np.ndarray,
               n_out: int) -> np.ndarray:
        c_in, c_out, k, s = self.layers[i]
        if self.formats is not None:
            _, wf, ai, af = self.formats[i]
            hq = _grid(h, ai, af)
        elif self.datapath == "bf16":
            hq = _round_float(h, ml_dtypes.bfloat16)
        else:
            hq = _round_float(h, ml_dtypes.float8_e4m3fn)
        idx = np.arange(k)[:, None] + s * np.arange(n_out)[None, :]
        cols = hq[:, idx].reshape(c_in * k, n_out)      # (c·K, n)
        acc = w.reshape(c_out, c_in * k) @ cols         # exact for integers
        if self.formats is not None:
            acc = acc * 2.0 ** -(wf + af)
        out = (acc + b[:, None]).astype(np.float32)
        if i < len(self.layers) - 1:
            out = np.maximum(out, np.float32(0))
        return out

    def __call__(self, x: np.ndarray, weights,
                 block_pos: int = 8192) -> np.ndarray:
        """Symbols of the stream x (1-D samples): len(x) // T · V_p."""
        x = np.asarray(x, np.float32).reshape(-1)
        n_pos = x.shape[0] // self.ts
        ws = self._weights(weights)
        need = _input_span(self.topo, max(n_pos, 1))
        xp = np.zeros((max(need, self.halo + x.shape[0]),), np.float32)
        xp[self.halo:self.halo + x.shape[0]] = x
        vp = self.layers[-1][1]
        y = np.empty((n_pos * vp,), np.float32)
        for p0 in range(0, n_pos, block_pos):
            n = min(block_pos, n_pos - p0)
            spans = [n]
            for _, _, k, s in reversed(self.layers):
                spans.append((spans[-1] - 1) * s + k)
            spans = spans[::-1]
            h = xp[p0 * self.ts:p0 * self.ts + spans[0]][None, :]
            for i, (w, b) in enumerate(ws):
                h = self._layer(i, h, w, b, spans[i + 1])
            y[p0 * vp:(p0 + n) * vp] = h.T.reshape(-1)
        return y


def max_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between two symbol sequences of one length."""
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    if got.size == 0:
        return 0.0
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got.astype(np.float64)
                               - want.astype(np.float64))))
