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
    Its ops carry the named scope `partition` in the device profile.
    """
    total = x_samples.shape[0]
    l_inst_samp = total // n_inst
    o_samp = o_act * n_os
    with jax.named_scope("partition"):
        xp = jnp.pad(x_samples, (o_samp, o_samp))
        starts = jnp.arange(n_inst) * l_inst_samp
        idx = starts[:, None] + jnp.arange(l_inst_samp + 2 * o_samp)[None, :]
        return xp[idx]


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
