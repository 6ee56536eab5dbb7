"""Stream partitioning with receptive-field overlap (paper §5.3 + §6.1).

The FPGA splits the symbol stream over N_i CNN instances through a binary tree
of split-stream modules (SSM); the overlap-generate module (OGM) prepends/
appends half a receptive field of context to every sub-sequence so the BER is
flat across chunk borders; merge-stream modules (MSM) + overlap-remove (ORM)
reassemble the output.

Here the same math drives two implementations:
  * this module — a pure-JAX reference split/merge (single device), used by
    tests as the oracle;
  * `repro.parallel.halo` — the TPU-native version, where each mesh device IS
    one "instance" and the overlap travels by `ppermute` halo exchange.

All lengths are in SYMBOLS unless suffixed `_samples` (waveforms carry
N_os samples per symbol).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .equalizer import CNNEqConfig


def overlap_symbols(cfg: CNNEqConfig) -> int:
    """o_sym = (K-1)(1 + V_p(L-1)) / 2 — half receptive field per side."""
    return (cfg.kernel - 1) * (1 + cfg.v_parallel * (cfg.layers - 1)) // 2


def _next_even(n: int) -> int:
    return n if n % 2 == 0 else n + 1


def actual_overlap(cfg: CNNEqConfig, n_inst: int) -> int:
    """o_act = nextEven(⌈o_sym / (V_p·N_i)⌉) · V_p · N_i  (paper §6.1).

    The overlap is added in front of the first SSM where the stream has width
    V_p·N_i and must be divisible by N_os (=2 ⇒ nextEven).
    """
    o_sym = overlap_symbols(cfg)
    return _next_even(math.ceil(o_sym / (cfg.v_parallel * n_inst))) \
        * cfg.v_parallel * n_inst


def chunk_lengths(total_syms: int, n_inst: int) -> int:
    """ℓ_inst: per-instance sub-sequence length (symbols)."""
    assert total_syms % n_inst == 0, "stream must divide across instances"
    return total_syms // n_inst


def split_with_overlap(x_samples: jnp.ndarray, n_inst: int, o_act: int,
                       n_os: int) -> jnp.ndarray:
    """Split waveform into n_inst overlapped chunks (OGM + SSM tree).

    x_samples: (S·N_os,) → (n_inst, (ℓ_inst + 2·o_act)·N_os)
    Stream edges are zero-padded (the FPGA pipeline likewise starts cold).

    Chunk i is samples [i·ℓ − o, (i+1)·ℓ + o) of the stream (ℓ = ℓ_inst·N_os,
    o = o_act·N_os), so it reaches r = ⌈o/ℓ⌉ blocks to each side. The
    stream is zero-padded by r·ℓ per side and reshaped into (n_inst + 2r, ℓ)
    blocks; row i of the view blocks[j : j + n_inst] is samples
    [(i − r + j)·ℓ, (i − r + j + 1)·ℓ). The chunk is the concat over
    j = 0..2r of what each view holds inside it: the last o − (r−1)·ℓ
    samples of view 0, the middle views whole, the first o − (r−1)·ℓ of
    view 2r. Every offset is static: a pad, a reshape, slices and one
    concat, with no index array and no gather.
    Samples past n_inst·ℓ (a stream that does not divide) feed only the
    last chunk's right overlap, as far as it reaches.
    Its ops carry the named scope `partition` in the device profile.
    """
    total = x_samples.shape[0]
    l_samp = total // n_inst
    o_samp = o_act * n_os
    reach = -(-o_samp // l_samp)
    with jax.named_scope("partition"):
        # negative right padding crops a tail the chunks never reach
        tail = total - n_inst * l_samp
        xp = jax.lax.pad(x_samples, jnp.zeros((), x_samples.dtype),
                         [(reach * l_samp, reach * l_samp - tail, 0)])
        blocks = xp.reshape(n_inst + 2 * reach, l_samp)
        lo = reach * l_samp - o_samp           # chunk start in view 0
        hi = lo + l_samp + 2 * o_samp
        return jnp.concatenate(
            [blocks[j:j + n_inst, max(lo - j * l_samp, 0):
                    min(hi - j * l_samp, l_samp)]
             for j in range(2 * reach + 1)], axis=1)


def merge_with_overlap_removal(chunks_syms: jnp.ndarray, o_act: int
                               ) -> jnp.ndarray:
    """MSM + ORM: drop o_act symbols at each side of each chunk, concat
    (named scope `merge`)."""
    with jax.named_scope("merge"):
        kept = chunks_syms[:, o_act:chunks_syms.shape[1] - o_act]
        return kept.reshape(-1)


def partitioned_apply(engine, x_samples: jnp.ndarray, n_inst: int,
                      cfg: CNNEqConfig) -> jnp.ndarray:
    """Run an equalizer over N_i instances with overlap — reference path.

    engine: the production path is a `repro.core.engine.EqualizerEngine`
    (any backend); any callable with the same contract — waveform chunks
    (batch, W) → symbols (batch, W//N_os) — also works, which the oracle
    tests use. Equivalent (on the interior) to running the engine on the
    unsplit stream: every kept symbol is ≥ o_act ≥ o_sym away from a chunk
    edge, so backend choice (ref / fused_fp32 / fused_int8) cannot change
    the merged result relative to the unsplit one.
    """
    o_act = actual_overlap(cfg, n_inst)
    chunks = split_with_overlap(x_samples, n_inst, o_act, cfg.n_os)
    y = engine(chunks)    # batched over instances via the engine's batch dim
    return merge_with_overlap_removal(y, o_act)
