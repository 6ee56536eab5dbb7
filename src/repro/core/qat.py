"""Learnable-bit-width quantization-aware training (paper §4).

The paper learns, per layer, a fixed-point format for weights and activations
by making the bit width differentiable:

  * integer width  i  and fraction width f are separate continuous parameters
    (this differs from BitPruning [20], which learns a scale; learning i and f
    directly means no rescaling is needed at deployment — values ARE their
    fixed-point representation),
  * quantization at non-integer width b interpolates between the two adjacent
    integer widths:  Q_b(x) = (1-α)·Q_⌊b⌋(x) + α·Q_⌈b⌉(x),  α = b - ⌊b⌋,
  * a straight-through estimator passes gradients through the rounding,
  * the loss gains  QLF · (B_p + B_a)/2  where B_p/B_a are the average
    parameter/activation widths.

Three-phase schedule (paper Fig. 5/6):
  1. full precision, 2. bit-width-aware (widths trained), 3. fine-tune with
  widths frozen to the next-highest integer.

TPU note (DESIGN.md §2): widths are *learned* exactly as on the FPGA; at
deployment the learned (i, f) map to the nearest native dtype (int8 /
bf16) — `deployment_dtype()` below.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class QATConfig:
    qlf: float = 5e-4             # quantization trade-off factor
    init_int_bits: float = 16.0   # phase-1 format: Q16.16
    init_frac_bits: float = 16.0
    min_bits: float = 1.0
    enabled: bool = True


# ---------------------------------------------------------------------------
# Fixed-point fake quantization
# ---------------------------------------------------------------------------

def _round_ste(x: jnp.ndarray) -> jnp.ndarray:
    """round() with a straight-through gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def quantize_fixed(x: jnp.ndarray, int_bits: jnp.ndarray,
                   frac_bits: jnp.ndarray) -> jnp.ndarray:
    """Fixed-point quantization to signed Q(int_bits).(frac_bits).

    Integer widths only — see `quantize_interp` for the differentiable-width
    version. STE on the rounding; clipping is naturally differentiable at the
    boundaries (clip gradient).
    """
    scale = jnp.exp2(frac_bits)
    hi = jnp.exp2(int_bits) - 1.0 / scale
    lo = -jnp.exp2(int_bits)
    xq = _round_ste(x * scale) / scale
    return jnp.clip(xq, lo, hi)


def quantize_interp(x: jnp.ndarray, int_bits: jnp.ndarray,
                    frac_bits: jnp.ndarray) -> jnp.ndarray:
    """Differentiable-width quantization via floor/ceil interpolation.

    Differentiable w.r.t. BOTH int_bits and frac_bits (and x via STE), so the
    widths can be learned with backprop — the paper's core quantization trick.
    """
    f_lo, f_hi = jnp.floor(frac_bits), jnp.ceil(frac_bits)
    a_f = frac_bits - f_lo
    i_lo, i_hi = jnp.floor(int_bits), jnp.ceil(int_bits)
    a_i = int_bits - i_lo
    q_ll = quantize_fixed(x, i_lo, f_lo)
    q_lh = quantize_fixed(x, i_lo, f_hi)
    q_hl = quantize_fixed(x, i_hi, f_lo)
    q_hh = quantize_fixed(x, i_hi, f_hi)
    q_l = (1 - a_f) * q_ll + a_f * q_lh
    q_h = (1 - a_f) * q_hl + a_f * q_hh
    return (1 - a_i) * q_l + a_i * q_h


# ---------------------------------------------------------------------------
# Per-layer quantizer parameter handling
# ---------------------------------------------------------------------------

def init_qparams(layer_names, cfg: QATConfig) -> Dict[str, Any]:
    """One (w_int, w_frac, a_int, a_frac) quadruple per layer."""
    mk = lambda v: jnp.asarray(v, jnp.float32)
    return {
        name: {
            "w_int": mk(cfg.init_int_bits), "w_frac": mk(cfg.init_frac_bits),
            "a_int": mk(cfg.init_int_bits), "a_frac": mk(cfg.init_frac_bits),
        }
        for name in layer_names
    }


def clip_qparams(qparams: Dict[str, Any], cfg: QATConfig) -> Dict[str, Any]:
    """Project widths onto the feasible region after an optimizer step."""
    return jax.tree.map(lambda b: jnp.clip(b, cfg.min_bits, 16.0), qparams)


def freeze_qparams(qparams: Dict[str, Any]) -> Dict[str, Any]:
    """Phase-3: fix widths to the next-highest integer (paper §4 step 3)."""
    return jax.tree.map(jnp.ceil, qparams)


def apply_weight_quant(w: jnp.ndarray, q: Dict[str, jnp.ndarray],
                       enabled: bool = True) -> jnp.ndarray:
    if not enabled:
        return w
    return quantize_interp(w, q["w_int"], q["w_frac"])


def apply_act_quant(a: jnp.ndarray, q: Dict[str, jnp.ndarray],
                    enabled: bool = True) -> jnp.ndarray:
    if not enabled:
        return a
    return quantize_interp(a, q["a_int"], q["a_frac"])


def average_bits(qparams: Dict[str, Any]):
    """(B_p, B_a): average total width of params / activations (+sign bit)."""
    w = [q["w_int"] + q["w_frac"] + 1.0 for q in qparams.values()]
    a = [q["a_int"] + q["a_frac"] + 1.0 for q in qparams.values()]
    return sum(w) / len(w), sum(a) / len(a)


def quant_loss_term(qparams: Dict[str, Any], cfg: QATConfig) -> jnp.ndarray:
    """QLF · (B_p + B_a) / 2 — the paper's quantization-aware loss term."""
    bp, ba = average_bits(qparams)
    return cfg.qlf * (bp + ba) / 2.0


def deployment_dtype(q: Dict[str, jnp.ndarray]) -> str:
    """Map a learned fixed-point format to the nearest TPU-native dtype."""
    total = float(q["w_int"] + q["w_frac"]) + 1.0
    if total <= 8:
        return "int8"
    if total <= 16:
        return "bfloat16"   # 8-bit exponent covers the int range; 8-bit mantissa
    return "float32"


def frozen_format(q: Dict[str, jnp.ndarray]):
    """Learned widths → concrete integer (w_int, w_frac, a_int, a_frac).

    Rounds UP like phase-3 freezing (`freeze_qparams`), so the deployed grid
    always covers the trained one. This is the per-layer fixed-point format
    the int8 fused kernel bakes in as its scales and clip bounds.
    """
    return (int(jnp.ceil(q["w_int"])), int(jnp.ceil(q["w_frac"])),
            int(jnp.ceil(q["a_int"])), int(jnp.ceil(q["a_frac"])))


def per_channel_formats(weights, formats):
    """Refine per-layer weight formats to per-OUTPUT-CHANNEL scales.

    The paper (and `frozen_format`) learns ONE (w_int, w_frac) per layer; a
    single channel with a large BN-fold gain then forces the whole layer
    onto a coarse grid. Per-channel refinement keeps each layer's learned
    TOTAL weight width (w_int + w_frac — the trained accuracy/width
    trade-off) but redistributes it per output channel: a channel whose
    folded weights are small narrows its integer width and reclaims the
    bits as fraction width (a finer grid). This costs no extra arithmetic —
    the integer products are unchanged; only the (already per-row) requantization
    scale becomes a per-channel vector (`repro.kernels.cnn_eq`).

    weights: BN-folded ((w, b), …) — per-channel ranges come from the
             DEPLOYED weights, exactly what the int8 kernel will quantize.
    formats: per-layer (w_int, w_frac, a_int, a_frac) from
             `layer_formats`/`deployment_plan` (scalars).

    Returns formats where w_int/w_frac are length-C_out tuples of ints
    (activation formats stay scalar — activations are requantized between
    layers on a shared grid). Layers whose every channel already needs the
    full learned integer width are returned unchanged (scalar).
    """
    out = []
    for (w, _), (wi, wf, ai, af) in zip(weights, formats):
        total = int(wi) + int(wf)            # magnitude bits, sign excluded
        wabs = np.max(np.abs(np.asarray(w, np.float64)).reshape(
            w.shape[0], -1), axis=1)
        wi_c = np.ceil(np.log2(np.maximum(wabs, 1e-12))).astype(np.int64)
        # never widen past the learned grid, never narrow absurdly (an
        # all-zero channel would otherwise get a 2^-40 grid and overflow
        # float scale math downstream)
        wi_c = np.clip(wi_c, int(wi) - 8, int(wi))
        # guarantee fit: Q(i).(f) tops out at 2^i − 2^−f, so a max right at
        # the power of two needs one more integer bit
        for c in range(wi_c.shape[0]):
            f_c = total - int(wi_c[c])
            if wabs[c] > 2.0 ** int(wi_c[c]) - 2.0 ** -f_c:
                wi_c[c] = min(int(wi_c[c]) + 1, int(wi))
        if np.all(wi_c == int(wi)):
            out.append((wi, wf, ai, af))     # nothing to reclaim
            continue
        out.append((tuple(int(v) for v in wi_c),
                    tuple(total - int(v) for v in wi_c), ai, af))
    return tuple(out)


def format_max_bits(wi, wf) -> int:
    """Worst-case total width (+sign) of a scalar OR per-channel format."""
    return int(np.max(np.asarray(wi) + np.asarray(wf))) + 1


def _layer_order(qparams: Dict[str, Any]):
    """'layer0' … 'layerN' keys in layer order (robust to dict ordering)."""
    return sorted(qparams, key=lambda n: int("".join(filter(str.isdigit, n))
                                             or 0))


def layer_formats(qparams: Dict[str, Any]):
    """Ordered tuple of frozen per-layer formats for the whole stack."""
    return tuple(frozen_format(qparams[n]) for n in _layer_order(qparams))


def _format_dtype(total_bits: int) -> str:
    if total_bits <= 8:
        return "int8"
    if total_bits <= 16:
        return "bfloat16"
    return "float32"


def plan_backend(plan: Dict[str, Any]) -> str:
    """Map a deployment plan to the engine backend that serves it natively.

    all layers int8        → "fused_int8"   (int8 grid, int32 sums)
    all layers ≤ 16 bits   → "fused_bf16"   (bf16 operands, fp32 sums —
                              bf16's exponent covers any learned int width,
                              its 8-bit mantissa the 9–16-bit fractions)
    anything wider         → "fused_fp32"
    """
    dts = set(plan["dtypes"].values())
    if dts <= {"int8"}:
        return "fused_int8"
    if dts <= {"int8", "bfloat16"}:
        return "fused_bf16"
    return "fused_fp32"


def deployment_plan(qparams: Dict[str, Any]) -> Dict[str, Any]:
    """Summarize how a trained quantizer deploys on the TPU datapath.

    Returns {"formats": ((w_int, w_frac, a_int, a_frac), …),
             "dtypes": {layer: dtype}, "all_int8": bool}. Unlike
    `deployment_dtype` (weight-only, raw learned widths), the per-layer
    dtype here uses the FROZEN formats and the wider of the weight and
    activation requirement — the same criterion as `all_int8` — so the
    record can never say "int8" for a layer the engine refuses to deploy.
    """
    names = _layer_order(qparams)
    formats = tuple(frozen_format(qparams[n]) for n in names)
    dtypes = {n: _format_dtype(max(wi + wf, ai + af) + 1)
              for n, (wi, wf, ai, af) in zip(names, formats)}
    all_int8 = all(d == "int8" for d in dtypes.values())
    return {"formats": formats, "dtypes": dtypes, "all_int8": all_int8}
