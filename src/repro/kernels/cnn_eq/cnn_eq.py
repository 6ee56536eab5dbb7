"""Pallas TPU kernel: the FUSED L-layer CNN equalizer (paper §5.1 on TPU).

The FPGA architecture instantiates each conv layer as a pipeline stage with
activations streaming between stages through on-chip FIFOs. The TPU-native
equivalent keeps the whole layer stack inside ONE kernel so inter-layer
activations never leave VMEM:

  HBM ──DMA──▶ VMEM input tile (with receptive-field halo)
                 │ conv1 (stride V_p) + ReLU        ┐ all in VMEM /
                 │ conv2 … conv_{L-1} + ReLU        │ vector registers —
                 │ conv_L (stride N_os)             ┘ zero HBM round-trips
  HBM ◀──DMA── VMEM output tile (V_p × tile_m symbols, channel-major)

Grid = (batch, sequence tiles): Mosaic overlaps the tile DMAs with compute,
which is exactly the paper's "each layer starts as soon as first inputs
arrive" streaming property, realized at tile granularity.

Layout. The wrapper hands each grid step its own input window (half a
receptive field of halo per side, `receptive_halo`) in POLYPHASE form: the
padded stream split into T = V_p·N_os phases, so that every tap of every
layer reads one phase at a static unit-stride offset (`_phase_plan`) — the
TPU compiler refuses strided lane slices, and interpret mode would not have
said so. The kernel computes VALID convolutions and the wrapper pre-pads the
stream so the result equals the SAME_LOWER-padded reference (`ref.cnn_eq`) —
including at stream edges. The output leaves the kernel channel-major and is
interleaved into symbol order by XLA.

Arithmetic. With C ≤ 8 channels a conv tap is far too small for the matrix
unit, so each tap is a handful of elementwise products on the vector unit,
added in the oracle's order (`ref.conv_valid_taps`). That fixes the
accumulation order on every backend: the fp32 kernel matches its oracle to
~2 ULP, the bf16 kernel (exact bf16 products) bitwise, and the int8 kernel
its fake-quant oracle exactly (integer arithmetic has no rounding freedom).

INT8 datapath (`cnn_eq_fused_int8`) — the deployment path when QAT's learned
per-layer fixed-point formats fit int8 (qat.deployment_dtype == "int8").
Weights are pre-quantized host-side to int8 at scale 2^w_frac; activations
are requantized INSIDE the kernel between layers, so the whole quantized
stack stays fused in VMEM:

      x (fp32 tile, VMEM)
        │ requant:  q = clip(round(x · 2^af₁))        → int8 grid
        │ conv1:    int8-grid products, int32 sums
        │ rescale:  acc · 2^-(wf₁+af₁) + b₁ (fp32)    → fp32
        │ ReLU ──▶ requant 2^af₂ ──▶ conv2 ──▶ … conv_L
        ▼
      y (fp32 symbols, VMEM)

The integer sums are exact (|w|·|a| ≤ 127·128, ΣC_in·K terms ≪ 2³¹) and the
rescale multiplies by a power of two, so the kernel reproduces the QAT
fake-quant reference (`ref.cnn_eq_quant`) exactly — quantization error comes
ONLY from the learned formats, never the kernel.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from .ref import receptive_halo


def _wformat_cols(wi, wf):
    """Weight-format components as broadcastable fp32 columns.

    wi/wf are static ints (one scale per layer, the paper's scheme) or
    per-output-channel tuples of ints (`qat.per_channel_formats`). Either
    way the result is a numpy column — shape (1, 1) or (C_out, 1) — that
    broadcasts over a (C_out, …) accumulator, so the scalar and per-channel
    paths share every downstream expression.
    """
    return (np.asarray(wi, np.float32).reshape(-1, 1),
            np.asarray(wf, np.float32).reshape(-1, 1))


def _phase_plan(kernels: Sequence[int], strides: Sequence[int],
                tile_m: int) -> Tuple[list[int], list[int]]:
    """Polyphase geometry of one tile.

    Level i (the input of layer i; level L = the output) keeps its positions
    split into phases[i] = T / (s_0···s_{i-1}) interleaved phases (T = ∏
    strides): phase r holds positions r, r + phases[i], …. With the input at
    T phases and the output at one, every tap of every layer reads ONE phase
    at a static, unit-stride column offset — no strided lane slices (which
    Mosaic refuses). cols[i] is the number of columns per phase at level i
    that tile_m final positions need.
    """
    phases = [int(np.prod(strides))]
    for s in strides:
        phases.append(phases[-1] // s)
    cols = [tile_m]
    for i in reversed(range(len(strides))):
        reach = (phases[i + 1] - 1) * strides[i] + kernels[i] - 1
        cols.append(cols[-1] + reach // phases[i])
    return phases, cols[::-1]


def _poly_conv(h, w_ref, stride: int, n_phases_out: int, n_cols: int):
    """One VALID conv layer on polyphase activations, on the vector unit.

    h: list of (C_in, cols) phase arrays and w_ref: (C_out, K·C_in) weight
    columns (column k·C_in + c is tap k, input channel c), both already in
    the accumulation dtype. Output position p = j·R' + r reads input
    p·stride + k = j·R + (r·stride + k), i.e. phase (r·stride + k) mod R at
    column j + (r·stride + k) div R. Each output element is the oracle's sum
    (`ref.conv_valid_taps`): taps k = 0 … K-1, each the products over input
    channels c = 0 … C_in-1 added in order. Elementwise products fix that
    order on every backend; a matrix unit's dot would not.
    """
    r_in, c_in = len(h), h[0].shape[0]
    w = w_ref[...]
    out = []
    for r in range(n_phases_out):
        acc = None
        for k in range(w.shape[1] // c_in):
            t = r * stride + k
            x = h[t % r_in][:, t // r_in:t // r_in + n_cols]
            term = None
            for c in range(c_in):
                j = k * c_in + c
                prod = w[:, j:j + 1] * x[c:c + 1]
                term = prod if term is None else term + prod
            acc = term if acc is None else acc + term
        out.append(acc)
    return out


def _input_phases(x_ref):
    """This tile's input as T phase rows of shape (1, in_cols)."""
    x = x_ref[...].astype(jnp.float32)                       # (T, in_cols)
    return [x[p:p + 1] for p in range(x.shape[0])]


def _cnn_eq_kernel(x_ref, *refs, tile_m: int, kernels, strides, operand):
    """Float kernel body. `operand` is the datapath: float32, or bfloat16 —
    activations and weights rounded to bf16, products and sums in fp32
    (a bf16·bf16 product is exact in fp32)."""
    n_layers = len(kernels)
    w_refs, b_refs, o_ref = refs[:-1][0::2], refs[:-1][1::2], refs[-1]
    phases, cols = _phase_plan(kernels, strides, tile_m)
    h = _input_phases(x_ref)
    for i in range(n_layers):
        h = [p.astype(operand).astype(jnp.float32) for p in h]
        b = b_refs[i][...]                                   # (C_out, 1)
        h = [acc + b for acc in _poly_conv(h, w_refs[i], strides[i],
                                           phases[i + 1], cols[i + 1])]
        if i < n_layers - 1:
            h = [jax.nn.relu(p) for p in h]
    o_ref[...] = h[0].astype(o_ref.dtype)                    # (V_p, tile_m)


def requant_int8(h: jnp.ndarray, a_int: int, a_frac: int) -> jnp.ndarray:
    """fp32 → int8 on the Q(a_int).(a_frac) grid (values are x·2^a_frac).

    Idempotent through `dequant_int8`: requant(dequant(q)) == q exactly
    (power-of-two scale, round of an on-grid value). The int8 kernel uses it
    between layers; `parallel.halo` uses it to ship int8 halo samples.
    """
    hi = float(2 ** (a_int + a_frac)) - 1.0
    lo = -float(2 ** (a_int + a_frac))
    q = jnp.clip(jnp.round(h * float(2.0 ** a_frac)), lo, hi)
    return q.astype(jnp.int8)


def dequant_int8(q: jnp.ndarray, a_frac: int) -> jnp.ndarray:
    """int8 grid values → fp32 real units (inverse scale of requant_int8)."""
    return q.astype(jnp.float32) * float(2.0 ** -a_frac)


_requant = requant_int8          # kernel-internal alias


def _cnn_eq_kernel_int8(x_ref, *refs, tile_m: int, kernels, strides,
                        formats):
    n_layers = len(kernels)
    body = refs[:-1]             # per layer: (w int32, b fp32, rescale fp32)
    w_refs = body[0::3]          # int8 grid weights (x 2^w_frac) as int32
    b_refs = body[1::3]          # fp32 biases (full-width accumulators)
    s_refs = body[2::3]          # (C_out, 1) exact power-of-two rescale —
    #   2^-(w_frac + a_frac) per OUTPUT CHANNEL. A uniform column for the
    #   paper's one-scale-per-layer scheme; genuinely per-channel for
    #   `qat.per_channel_formats` deployments. Either way the integer
    #   products below are identical — per-channel scales cost only this
    #   rescale column (Pallas cannot capture array constants, hence an
    #   operand rather than a baked-in value).
    o_ref = refs[-1]
    phases, cols = _phase_plan(kernels, strides, tile_m)
    h = _input_phases(x_ref)
    for i in range(n_layers):
        _, _, ai, af = formats[i]
        # fused requantization; int32 products and sums are exact
        hq = [_requant(p, ai, af).astype(jnp.int32) for p in h]
        scale = s_refs[i][...]
        b = b_refs[i][...]
        # exact power-of-two rescale back to real units, then fp32 bias
        h = [acc.astype(jnp.float32) * scale + b
             for acc in _poly_conv(hq, w_refs[i], strides[i], phases[i + 1],
                                   cols[i + 1])]
        if i < n_layers - 1:
            h = [jax.nn.relu(p) for p in h]
    o_ref[...] = h[0].astype(o_ref.dtype)


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret=None` means interpret mode exactly when JAX's default
    backend is the CPU; an explicit bool is taken as given."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _operand_layout(w, b):
    """(…, C_out, C_in, K) weights and (…, C_out) biases → the kernel's
    operand layout: (…, C_out, K·C_in) weight columns (column k·C_in + c is
    tap k, input channel c) and (…, C_out, 1) float32 bias columns."""
    w = jnp.swapaxes(w, -1, -2)                    # (…, C_out, K, C_in)
    w = w.reshape(w.shape[:-2] + (-1,))            # (…, C_out, K·C_in)
    return w, b.astype(jnp.float32)[..., None]     # (…, C_out, 1)


def _table_kernels(table) -> Tuple[int, ...]:
    """Kernel sizes of a weight table's layers: layer 0 reads the one input
    channel, layer i the C_out of layer i-1."""
    kernels, c_in = [], 1
    for item in table:
        kernels.append(int(item[0].shape[-1]) // c_in)
        c_in = int(item[0].shape[-2])
    return tuple(kernels)


def _fused_call(kernel_body, x, weights, strides, tile_m, interpret, name,
                slots=None, **kernel_kwargs):
    """Shared grid/BlockSpec plumbing for all fused kernel bodies.

    `name` is the kernel's stable name in compiled programs and device
    profiles (`cnn_eq_fused_{fp32,bf16,int8}`, one per datapath). The
    wrapper's own ops carry the named scopes `tile_windows` (the per-tile
    input windows) and `interleave` (channel-major tiles to symbol order).

    Weights are either SHARED — w: (C_out, C_in, K), b: (C_out,) broadcast
    to every batch row, with `slots` None — or a TABLE of per-row weight
    sets already in the operand layout (`table_rows`) — w: (N, C_out,
    K·C_in), b: (N, C_out, 1) — with `slots` an int32 (B,) vector: batch
    row i is computed with table entry slots[i]. The table form is the
    multi-tenant serving path: one launch, per-tenant weights selected by
    the BlockSpec index map from the scalar-prefetched slot vector, with
    no weight op in the launch. Each row is bitwise what the shared form
    computes with that entry's weights alone: same kernel body, same
    block shapes; only the weights' index map differs.

    Layouts the TPU compiler accepts, all built here by XLA:
      * input: per-tile windows of the padded stream in T = ∏strides
        phases, (B, n_tiles, T, in_cols), so every in-kernel tap is a
        static unit-stride slice and no load needs an aligned offset;
      * weights: one (C_out, K·C_in) matrix per layer, biases and
        rescales as (C_out, 1) columns (`_operand_layout`); the table dim
        of a table's operands is squeezed;
      * output: channel-major (B, n_tiles, V_p, tile_m) tiles whose last two
        block dims equal the array's, so every tile_m ≥ 1 is a legal block;
        the symbol interleave s = m·V_p + c happens after the kernel.
    """
    interpret = resolve_interpret(interpret)
    if tile_m < 1:
        raise ValueError(f"tile_m must be a positive int, got {tile_m}")
    batch, width = x.shape
    if slots is None:
        kernels = tuple(int(item[0].shape[-1]) for item in weights)
        v_parallel = int(weights[-1][0].shape[-3])
    else:
        if tuple(slots.shape) != (batch,):
            raise ValueError(f"slots has shape {tuple(slots.shape)}, "
                             f"x has batch {batch}")
        kernels = _table_kernels(weights)
        v_parallel = int(weights[-1][0].shape[-2])
    total_stride = int(np.prod(strides))
    n_pos = width // total_stride                  # final-layer positions
    n_syms = n_pos * v_parallel

    # Always tile at the REQUESTED tile_m — even for a stream shorter than
    # one tile: the serving chunker buckets launches at whole tiles, and a
    # launch must compute every position with the same program shapes as
    # the offline call for chunked==offline bitwise equality (contract #4).
    # Short streams just compute a few extra padded positions that the
    # final n_syms slice drops.
    n_tiles = pl.cdiv(n_pos, tile_m)
    halo = receptive_halo(kernels, strides)
    in_cols = _phase_plan(kernels, strides, tile_m)[1][0]

    # Tile-local input windows in phase layout, (B, n_tiles, T, in_cols):
    # window `it` is phase columns [it·tile_m, it·tile_m + in_cols) of the
    # padded stream, built from the `reach` following tile blocks (slices
    # and a concat, no gather). Halo on the left, zeros on the right; any
    # samples past the last window feed no kept position.
    reach = pl.cdiv(in_cols - tile_m, tile_m)
    padded = (n_tiles + reach) * tile_m * total_stride
    with jax.named_scope("tile_windows"):
        xp = jnp.pad(x, ((0, 0), (halo, max(0, padded - width - halo))))
        blocks = xp[:, :padded].reshape(batch, n_tiles + reach, tile_m,
                                        total_stride)
        xp = jnp.concatenate(
            [blocks[:, j:j + n_tiles] for j in range(reach + 1)],
            axis=2)[:, :, :in_cols]
        xp = jnp.swapaxes(xp, 2, 3)                # (B, n_tiles, T, in_cols)

    # index maps take (ib, it) and, in a table launch, the slot vector
    def full(shape):
        return pl.BlockSpec(shape, lambda ib, it, *_: (0,) * len(shape))

    def per_row(shape):
        return pl.BlockSpec((None,) + shape,
                            lambda ib, it, slots: (slots[ib],)
                            + (0,) * len(shape))

    flat: list[jnp.ndarray] = [xp]
    in_specs = [pl.BlockSpec((None, None) + xp.shape[2:],
                             lambda ib, it, *_: (ib, it, 0, 0))]
    for item in weights:
        if slots is None:
            w, b = _operand_layout(item[0], item[1])
            spec = full
        else:
            w, b = item[0], item[1]
            spec = per_row
        flat += [w, b]
        in_specs += [spec(w.shape[-2:]), spec(b.shape[-2:])]
        # trailing per-layer operands (e.g. the int8 rescale column) are
        # SHARED across batch rows even in table launches: they derive
        # from the static formats, which every engine in a group shares
        # (formats are part of group_key)
        for extra in item[2:]:
            flat.append(extra[:, None])
            in_specs.append(full(flat[-1].shape))

    body = functools.partial(kernel_body, tile_m=tile_m, kernels=kernels,
                             strides=strides, **kernel_kwargs)
    out_specs = pl.BlockSpec((None, None, v_parallel, tile_m),
                             lambda ib, it, *_: (ib, it, 0, 0))
    out_shape = jax.ShapeDtypeStruct((batch, n_tiles, v_parallel, tile_m),
                                     x.dtype)
    if slots is None:
        out = pl.pallas_call(body, grid=(batch, n_tiles), in_specs=in_specs,
                             out_specs=out_specs, out_shape=out_shape,
                             interpret=interpret, name=name)(*flat)
    else:
        out = pl.pallas_call(
            lambda slots_ref, *refs: body(*refs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(batch, n_tiles),
                in_specs=in_specs, out_specs=out_specs),
            out_shape=out_shape, interpret=interpret,
            name=name)(slots.astype(jnp.int32), *flat)
    # (B, n_tiles, V_p, tile_m) → interleave channels: symbol s = m·V_p + c
    with jax.named_scope("interleave"):
        out = jnp.swapaxes(out, 2, 3).reshape(
            batch, n_tiles * tile_m * v_parallel)
        return out[:, :n_syms]


@functools.partial(jax.jit,
                   static_argnames=("strides", "tile_m", "interpret"))
def cnn_eq_fused(x: jnp.ndarray,
                 weights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...],
                 strides: Tuple[int, ...], tile_m: int = 64,
                 interpret: bool | None = None,
                 slots: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused fp32 equalizer forward. x: (B, W) → (B, W//N_os) symbols.

    weights: ((w_1, b_1), …, (w_L, b_L)) — BN pre-folded (equalizer.fold_bn),
    shared by every batch row (w: (C_out, C_in, K)); or, with `slots` an
    int32 (B,) vector, a weight table (`table_rows` rows stacked) whose
    entry slots[i] row i runs through — see `_fused_call`.
    strides: (V_p, 1, …, N_os). Output length = W // (V_p·N_os) · V_p.
    """
    return _fused_call(_cnn_eq_kernel, x, weights, strides, tile_m, interpret,
                       "cnn_eq_fused_fp32", slots=slots, operand=jnp.float32)


def cast_weights_bf16(
        weights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...],
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...]:
    """Host-side bf16 deployment cast: fp32 folded weights → bf16; biases
    stay fp32 (full-width accumulators, like the int8 path)."""
    return tuple((w.astype(jnp.bfloat16), b.astype(jnp.float32))
                 for w, b in weights)


@functools.partial(jax.jit,
                   static_argnames=("strides", "tile_m", "interpret"))
def cnn_eq_fused_bf16(x: jnp.ndarray,
                      bweights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...],
                      strides: Tuple[int, ...], tile_m: int = 64,
                      interpret: bool | None = None,
                      slots: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused bf16 equalizer forward: bf16 operands, fp32 accumulation.

    The deployment path for QAT formats in the 9–16-bit range
    (`qat.deployment_dtype() == "bfloat16"`). bweights from
    `cast_weights_bf16` (fp32 weights are accepted and rounded to bf16).
    Matches the pure-jnp oracle `ref.cnn_eq_bf16` bitwise: bf16 products
    are exact in fp32 and both add them in the same order. Weights shared
    by every batch row, or a weight table with `slots`, like
    `cnn_eq_fused`; a table holds the rounded weights already.
    """
    if slots is None:
        bweights = tuple((w.astype(jnp.bfloat16).astype(jnp.float32), b)
                         for w, b in bweights)
    return _fused_call(_cnn_eq_kernel, x, bweights, strides, tile_m,
                       interpret, "cnn_eq_fused_bf16", slots=slots,
                       operand=jnp.bfloat16)


def quantize_weights_int8(
        weights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...],
        formats: Tuple[Tuple[int, int, int, int], ...],
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...]:
    """Host-side weight quantization: fp32 folded weights → int8 at 2^w_frac.

    formats[l] = (w_int, w_frac, a_int, a_frac); requires w_int+w_frac+1 ≤ 8
    (qat.deployment_dtype == "int8"). Biases stay fp32. w_int/w_frac may be
    per-output-channel tuples (`qat.per_channel_formats`) — each channel is
    then quantized on its own 2^w_frac[c] grid; the kernel undoes the
    per-channel scale in its requantization column.
    """
    out = []
    for (w, b), (wi, wf, _, _) in zip(weights, formats):
        wi_col, wf_col = _wformat_cols(wi, wf)
        bits = int(np.max(wi_col + wf_col)) + 1
        if bits > 8:
            raise ValueError(
                f"format Q{wi}.{wf} needs {bits} bits > int8")
        shape = (-1, 1, 1)                     # broadcast over (C_out, C_in, K)
        hi = np.exp2(wi_col + wf_col).reshape(shape) - 1.0
        lo = -np.exp2(wi_col + wf_col).reshape(shape)
        scale = np.exp2(wf_col).reshape(shape)
        wq = jnp.clip(jnp.round(w.astype(jnp.float32) * scale),
                      lo, hi).astype(jnp.int8)
        out.append((wq, b.astype(jnp.float32)))
    return tuple(out)


@functools.partial(jax.jit,
                   static_argnames=("strides", "formats", "tile_m",
                                    "interpret"))
def cnn_eq_fused_int8(x: jnp.ndarray,
                      qweights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...],
                      strides: Tuple[int, ...],
                      formats: Tuple[Tuple[int, int, int, int], ...],
                      tile_m: int = 64,
                      interpret: bool | None = None,
                      slots: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused INT8 equalizer forward (see module docstring datapath diagram).

    qweights: ((w_q int8, b fp32), …) from `quantize_weights_int8`, shared
              by every batch row; or, with `slots`, a weight table like
              `cnn_eq_fused`'s (int32 grid values).
    formats:  per-layer (w_int, w_frac, a_int, a_frac) — static, baked into
              the kernel as requant scales/clip bounds; w_int/w_frac may be
              per-output-channel tuples. Every format must fit a signed
              8-bit grid: the in-kernel requant casts to int8, which would
              silently WRAP (not saturate) wider grids.
    """
    _check_int8_formats(formats)
    c_out = -3 if slots is None else -2
    withscale = tuple((w.astype(jnp.int32), b,
                       _int8_rescale(int(w.shape[c_out]), fmt))
                      for (w, b), fmt in zip(qweights, formats))
    return _fused_call(_cnn_eq_kernel_int8, x, withscale, strides,
                       tile_m, interpret, "cnn_eq_fused_int8", slots=slots,
                       formats=formats)


def _check_int8_formats(formats) -> None:
    """Every format must fit a signed 8-bit grid: the in-kernel requant
    casts to int8, which would silently WRAP (not saturate) wider grids."""
    for i, (wi, wf, ai, af) in enumerate(formats):
        wi_col, wf_col = _wformat_cols(wi, wf)
        if int(np.max(wi_col + wf_col)) + 1 > 8 or ai + af + 1 > 8:
            raise ValueError(
                f"layer {i} format (Q{wi}.{wf} w / Q{ai}.{af} a) does not "
                f"fit int8; the int8 requant would wrap silently")


def _int8_rescale(c_out: int, fmt) -> jnp.ndarray:
    """A layer's rescale column 2^-(w_frac + a_frac), broadcast to
    (C_out,) — Pallas kernels cannot capture array constants, so the
    (possibly per-channel) scale travels as a third per-layer operand."""
    wi, wf, _, af = fmt
    _, wf_col = _wformat_cols(wi, wf)
    return jnp.asarray(np.broadcast_to(np.exp2(-(wf_col + af)).reshape(-1),
                                       (c_out,)).astype(np.float32))


def table_rows(weights, backend: str):
    """One engine's deployed layer weights → the rows a weight table holds
    for it: per layer (w (C_out, K·C_in), b (C_out, 1)) in the operand
    layout, with exactly the values the shared-weights wrapper of `backend`
    hands the kernel — fp32 as given, bf16 rounded to bf16 and widened to
    fp32, int8 widened to int32.

    weights: `EqualizerEngine._layer_weights()` — fp32 folded weights,
    `cast_weights_bf16` or `quantize_weights_int8` output."""
    rows = []
    for w, b in weights:
        if backend == "fused_bf16":
            w = w.astype(jnp.bfloat16).astype(jnp.float32)
        elif backend == "fused_int8":
            w = w.astype(jnp.int32)
        rows.append(_operand_layout(w, b))
    return tuple(rows)
