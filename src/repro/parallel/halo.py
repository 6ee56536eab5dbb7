"""Halo-exchange sequence parallelism — the paper's stream partitioning
(SSM/MSM/OGM/ORM, §5.3) as a TPU-native `shard_map`.

FPGA → TPU mapping (DESIGN.md §2):

    N_i CNN instances            →  devices along one mesh axis
    SSM/MSM binary split tree    →  the mesh axis itself (data is *already*
                                    resident per device — no tree needed)
    OGM overlap generation       →  `ppermute` halo exchange: each device
                                    sends its left/right boundary samples to
                                    its neighbours (2·o_act symbols total per
                                    device instead of re-streaming whole
                                    overlapped windows — strictly less
                                    traffic than the FPGA scheme)
    ORM overlap removal          →  each device drops the halo after compute

The halo width is the receptive-field formula of paper §6.1 (via
core.stream_partition.actual_overlap), generalized by `halo_samples` for any
finite-receptive-field layer (CNN equalizer, Mamba2 conv, SWA attention).

`halo_apply` is the public entry: it wraps the production
`repro.core.engine.EqualizerEngine` (or any per-chunk callable,
waveform → symbols) so the sharded result equals the unsharded oracle
exactly — asserted by tests/test_halo.py. Each mesh device runs the
engine's fused kernel on its chunk, so the paper's two parallelism axes
compose: N_i instances (mesh) × fused tiling (kernel grid).

With a fused_int8 engine the halo itself travels as int8: the boundary
samples are requantized to the engine's layer-0 activation grid before the
`ppermute` and dequantized on arrival — 4× less exchange traffic, bit-
identical output (the kernel requantizes its inputs to the same grid
anyway; requantization is idempotent).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.equalizer import CNNEqConfig
from ..core.stream_partition import actual_overlap


def halo_exchange(x: jnp.ndarray, halo: int, axis_name: str,
                  quant: Optional[Tuple[int, int]] = None) -> jnp.ndarray:
    """Exchange `halo` boundary elements with both neighbours.

    x: per-device chunk (..., W). Returns (..., W + 2·halo) with the
    neighbours' boundary samples attached (zeros at the stream edges,
    matching the FPGA's cold pipeline start).

    quant: optional (a_int, a_frac) — the consumer's LAYER-0 activation
    format. When set, the edges are requantized to int8 on that grid
    BEFORE the ppermute and dequantized on arrival, cutting the exchange
    traffic 4× vs fp32. Lossless for the int8 fused engine: its kernel
    requantizes every input sample to the same grid on entry, and requant
    is idempotent (round/clip of an on-grid value is the identity), so the
    equalized output is bit-identical to exchanging fp32 samples.
    """
    n = jax.lax.psum(1, axis_name)
    if halo == 0 or n == 1:
        pad = [(0, 0)] * (x.ndim - 1) + [(halo, halo)]
        return jnp.pad(x, pad)
    if quant is not None:
        from ..kernels.cnn_eq.cnn_eq import dequant_int8, requant_int8
        a_int, a_frac = quant
        pack = lambda e: requant_int8(e, a_int, a_frac)      # fp32 → int8
        unpack = lambda q: dequant_int8(q, a_frac)           # int8 → fp32
    else:
        pack = unpack = lambda e: e
    # send my RIGHT edge to my right neighbour (it becomes their LEFT halo)
    right_edge = pack(x[..., -halo:])
    left_halo = unpack(jax.lax.ppermute(
        right_edge, axis_name, [(i, (i + 1) % n) for i in range(n)]))
    # send my LEFT edge to my left neighbour (their RIGHT halo)
    left_edge = pack(x[..., :halo])
    right_halo = unpack(jax.lax.ppermute(
        left_edge, axis_name, [(i, (i - 1) % n) for i in range(n)]))
    idx = jax.lax.axis_index(axis_name)
    # stream edges: first device has no left context, last has no right
    left_halo = jnp.where(idx == 0, jnp.zeros_like(left_halo), left_halo)
    right_halo = jnp.where(idx == n - 1, jnp.zeros_like(right_halo),
                           right_halo)
    return jnp.concatenate([left_halo, x, right_halo], axis=-1)


def _engine_halo_quant(apply_fn) -> Optional[Tuple[int, int]]:
    """(a_int, a_frac) of the engine's FIRST layer when the int8 exchange
    is lossless — i.e. apply_fn is a fused_int8 `EqualizerEngine` (duck-
    typed to keep halo importable without core.engine)."""
    if getattr(apply_fn, "backend", None) != "fused_int8":
        return None
    formats = getattr(apply_fn, "formats", None)
    if not formats:
        return None
    _, _, a_int, a_frac = formats[0]
    return (int(a_int), int(a_frac))


def halo_samples(cfg: CNNEqConfig, n_inst: int) -> int:
    """o_act in SAMPLES (the paper's o_act is in symbols; waveform carries
    N_os samples per symbol)."""
    return actual_overlap(cfg, n_inst) * cfg.n_os


def halo_apply(apply_fn: Callable[[jnp.ndarray], jnp.ndarray],
               x: jnp.ndarray, cfg: CNNEqConfig, mesh: Mesh,
               axis: str = "data") -> jnp.ndarray:
    """Equalize a waveform stream sharded over `axis` of `mesh`.

    apply_fn: an `EqualizerEngine` (the production path) or any callable
    (batch=1, W_chunk) waveform → (1, W_chunk // N_os) symbols — must have
    a receptive field ≤ the §6.1 overlap (true for the CNN equalizer by
    construction).
    x: (S·N_os,) the full waveform (sharded or shardable over `axis`).
    Returns (S,) symbols, identical to apply_fn on the unsplit stream.
    """
    n_inst = mesh.shape[axis]
    o_samp = halo_samples(cfg, n_inst)
    o_sym = o_samp // cfg.n_os
    quant = _engine_halo_quant(apply_fn)      # int8 engine → int8 traffic

    def per_device(chunk):
        # chunk: (W_local,) — one "CNN instance" of the paper
        ext = halo_exchange(chunk[None, :], o_samp, axis, quant)  # OGM
        y = apply_fn(ext)                                     # CNN instance
        return y[0, o_sym:y.shape[1] - o_sym]                 # ORM

    # check_vma=False: pallas_call (the fused backends) carries no
    # varying-manual-axes rule; all specs here are fully partitioned so
    # nothing is lost.
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis), check_vma=False)
    return fn(x)


def halo_apply_batched(apply_fn: Callable, x: jnp.ndarray,
                       cfg: CNNEqConfig, mesh: Mesh,
                       axis: str = "data") -> jnp.ndarray:
    """(B, S·N_os) variant: batch stays replicated-or-batch-sharded on other
    axes; the stream dim is halo-sharded over `axis`."""
    n_inst = mesh.shape[axis]
    o_samp = halo_samples(cfg, n_inst)
    o_sym = o_samp // cfg.n_os
    quant = _engine_halo_quant(apply_fn)

    def per_device(chunk):
        ext = halo_exchange(chunk, o_samp, axis, quant)
        y = apply_fn(ext)
        return y[:, o_sym:y.shape[1] - o_sym]

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=P(None, axis),
                       out_specs=P(None, axis), check_vma=False)
    return fn(x)
