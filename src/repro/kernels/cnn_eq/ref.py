"""Pure-jnp oracle for the fused CNN-equalizer kernel (fp32 + int8 paths).

STREAM semantics (matching the FPGA and the Pallas kernel): the input is
padded ONCE with half a receptive field of zeros per side and the layer stack
runs VALID convolutions — there is no per-layer zero padding, because on the
streaming hardware the layers see a continuous activation stream.

This differs from `repro.core.equalizer.apply_folded` (per-layer SAME
padding, the training-time definition) ONLY within o_sym symbols of the
stream edges — exactly the region the paper's overlap machinery discards.
tests/test_kernels.py asserts: kernel == ref everywhere, and
kernel == core-module on the interior.

The convolutions here are TAP-UNROLLED (`conv_valid_taps`) and spell out
every product: tap k over a strided slice of the whole stream contributes
Σ_c w[:, c, k] ⊗ x_c, the channels added c = 0 … C_in-1, and the taps are
added k = 0 … K-1. There is no dot: a dot leaves its accumulation order and
precision to the backend (an fp32 dot at DEFAULT precision is one bf16 pass
on a TPU), while elementwise products and ordered adds mean the same thing
on the CPU and on the chip. The Pallas kernel states the same sums on a
polyphase layout of its tiles (unit-stride taps, the layout the TPU compiler
accepts) — per output element the same products added in the same order —
so neither layout nor tiling changes the math. The fused fp32 kernel agrees
with this oracle to within ~2 ULP (XLA may contract a mul+add into an FMA
on one side and not the other; tests assert atol=5e-6). bf16 products are
exact in fp32, so the bf16 kernel matches `cnn_eq_bf16` bitwise; the int8
path is integer arithmetic and reproduces its oracle EXACTLY.

`cnn_eq_quant` is the QAT fake-quant oracle for the int8 datapath: weights
and per-layer input activations are snapped to their learned fixed-point
grids (core/qat.quantize_fixed) and the convs run in fp32. The int8 Pallas
kernel computes the same values with integer arithmetic + power-of-two
rescaling; tests assert agreement within one accumulation LSB.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def receptive_halo(kernels: Sequence[int], strides: Sequence[int]) -> int:
    r, jump = 0, 1
    for k, s in zip(kernels, strides):
        r += (k // 2) * jump
        jump *= s
    return r


def conv_valid_taps(h: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                    stride: int, n_out: int) -> jnp.ndarray:
    """(C_in, W) ⊛ (C_out, C_in, K) → (C_out, n_out) in fp32.

    The oracle's definition of one equalizer conv layer: for each tap k,
    the products w[:, c, k] · x[c, k + stride·n] summed over c in order,
    then the taps summed in order, then the bias.
    """
    h = h.astype(jnp.float32)
    w = w.astype(jnp.float32)
    acc = None
    for kk in range(w.shape[-1]):
        xk = jax.lax.slice(h, (0, kk),
                           (h.shape[0], kk + (n_out - 1) * stride + 1),
                           (1, stride))
        term = None
        for c in range(h.shape[0]):
            prod = w[:, c, kk][:, None] * xk[c][None, :]
            term = prod if term is None else term + prod
        acc = term if acc is None else acc + term
    return acc + b.astype(jnp.float32)[:, None]


def conv_valid_taps_bf16(h: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                         stride: int, n_out: int) -> jnp.ndarray:
    """bf16 variant of `conv_valid_taps`: bf16 operands, fp32 accumulation.

    Inputs and weights are rounded to bfloat16 before the layer (weights may
    already be bf16 — the rounding is then a no-op); products, sums, the
    bias add and the activations BETWEEN layers stay fp32. A bf16·bf16
    product is exact in fp32. This is the deployment datapath for QAT
    formats in the 9–16-bit range (`qat.deployment_dtype() == "bfloat16"`):
    bf16's 8-bit mantissa covers the learned fraction widths and its
    exponent covers any integer width, so no clipping/saturation logic is
    needed. The oracle of the fused bf16 kernel (`cnn_eq_bf16`).
    """
    return conv_valid_taps(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                           b, stride, n_out)


def _halo_pad(x: jnp.ndarray, kernels: Sequence[int],
              strides: Sequence[int]):
    """Stream-semantics padding shared by every oracle: ONE halo of zeros
    on the left, zeros on the right up to the last position's window."""
    halo = receptive_halo(kernels, strides)
    total_stride = 1
    for s in strides:
        total_stride *= s
    n_pos = x.shape[1] // total_stride
    need = (n_pos - 1) * total_stride + 2 * halo + 1
    xp = jnp.pad(x, ((0, 0), (halo, max(0, need - x.shape[1] - halo))))
    return xp, n_pos


def _stack_valid(x_row: jnp.ndarray,
                 weights: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
                 strides: Sequence[int], n_pos: int,
                 conv_fn=conv_valid_taps) -> jnp.ndarray:
    """Run the halo-padded layer stack on one stream: (W_pad,) → (n_syms,).

    conv_fn picks the datapath: `conv_valid_taps` (fp32, the default) or
    `conv_valid_taps_bf16` — the surrounding span/ReLU machinery is the
    single shared definition of stream semantics.
    """
    n_layers = len(weights)
    spans = [n_pos]
    for (w, _), s in zip(reversed(list(weights)), reversed(list(strides))):
        spans.append((spans[-1] - 1) * s + int(w.shape[-1]))
    spans = spans[::-1]
    h = x_row[None, :].astype(jnp.float32)          # (C_in=1, W_pad)
    for i, ((w, b), s) in enumerate(zip(weights, strides)):
        h = conv_fn(h, w, b, s, spans[i + 1])
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return jnp.swapaxes(h, 0, 1).reshape(-1)        # (n_pos · V_p,)


def cnn_eq(x: jnp.ndarray, weights: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
           strides: Sequence[int]) -> jnp.ndarray:
    """x: (B, W) waveform → (B, W//(∏strides)·V_p) symbols (stream semantics)."""
    kernels = [int(w.shape[-1]) for w, _ in weights]
    xp, n_pos = _halo_pad(x, kernels, strides)
    y = jax.vmap(lambda row: _stack_valid(row, weights, strides, n_pos))(xp)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# QAT fake-quant oracle (int8 datapath reference)
# ---------------------------------------------------------------------------

def _fake_quant(x: jnp.ndarray, int_bits, frac_bits) -> jnp.ndarray:
    """quantize_fixed without the STE (forward values are identical).

    int_bits/frac_bits are python ints, or arrays broadcastable against `x`
    (the per-output-channel weight-scale path: shape (C_out, 1, 1))."""
    scale = np.exp2(np.asarray(frac_bits, np.float32))
    hi = np.exp2(np.asarray(int_bits, np.float32)) - 1.0 / scale
    lo = -np.exp2(np.asarray(int_bits, np.float32))
    return jnp.clip(jnp.round(x * scale) / scale, lo, hi)


def cnn_eq_quant(x: jnp.ndarray,
                 weights: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
                 strides: Sequence[int],
                 formats: Sequence[Tuple[int, int, int, int]]) -> jnp.ndarray:
    """Fake-quantized stream-semantics forward — the int8 kernel's oracle.

    formats[l] = (w_int, w_frac, a_int, a_frac): the frozen per-layer
    fixed-point formats from QAT. Layer l snaps its input activations to
    Q(a_int).(a_frac) and its (BN-folded) weights to Q(w_int).(w_frac),
    exactly like `core.equalizer.apply` with qat_enabled, then convolves in
    fp32. Biases stay fp32 (the FPGA keeps full-width accumulators).
    """
    kernels = [int(w.shape[-1]) for w, _ in weights]
    xp, n_pos = _halo_pad(x, kernels, strides)

    spans = [n_pos]
    for k, s in zip(reversed(kernels), reversed(list(strides))):
        spans.append((spans[-1] - 1) * s + k)
    spans = spans[::-1]

    n_layers = len(weights)

    def one(row):
        h = row[None, :].astype(jnp.float32)
        for i, ((w, b), s) in enumerate(zip(weights, strides)):
            wi, wf, ai, af = formats[i]
            # scalar or per-output-channel weight formats: reshape to a
            # (C_out|1, 1, 1) column so both broadcast over (C_out, C_in, K)
            wi_col = np.asarray(wi, np.float32).reshape(-1, 1, 1)
            wf_col = np.asarray(wf, np.float32).reshape(-1, 1, 1)
            wq = _fake_quant(w.astype(jnp.float32), wi_col, wf_col)
            h = _fake_quant(h, ai, af)
            h = conv_valid_taps(h, wq, b, s, spans[i + 1])
            if i < n_layers - 1:
                h = jax.nn.relu(h)
        return jnp.swapaxes(h, 0, 1).reshape(-1)

    return jax.vmap(one)(xp).astype(x.dtype)


def cnn_eq_bf16(x: jnp.ndarray,
                weights: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
                strides: Sequence[int]) -> jnp.ndarray:
    """bf16-datapath stream-semantics forward — the fused_bf16 oracle.

    Same halo/VALID structure as `cnn_eq` (shared `_stack_valid`
    machinery), but every conv runs through `conv_valid_taps_bf16` (bf16
    dots, fp32 accum). Weights may be fp32 (cast there) or pre-cast bf16
    (the engine's deployment form) — both give identical results because
    the cast is idempotent.
    """
    kernels = [int(w.shape[-1]) for w, _ in weights]
    xp, n_pos = _halo_pad(x, kernels, strides)
    y = jax.vmap(lambda row: _stack_valid(row, weights, strides, n_pos,
                                          conv_fn=conv_valid_taps_bf16))(xp)
    return y.astype(x.dtype)
