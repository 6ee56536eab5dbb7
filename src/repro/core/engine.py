"""EqualizerEngine — the single production inference path.

Everything downstream of training funnels through this object: stream
partitioning (`core.stream_partition.partitioned_apply`), halo-exchange
sharding (`parallel.halo.halo_apply`), the examples, and the equalizer
benchmarks all consume an engine instead of hand-rolled `apply_folded`
lambdas. The engine owns:

  * BN folding (done once, at construction — the FPGA deployment step),
  * backend selection:
      - "ref"        pure-jnp stream-semantics oracle (kernels.cnn_eq.ref),
      - "fused_fp32" the fused Pallas kernel — same math as "ref",
      - "fused_bf16" the fused Pallas kernel with bf16 operands and fp32
        products and sums — the native datapath for QAT formats in the
        9–16-bit range (qat.deployment_dtype == "bfloat16"),
      - "fused_int8" the quantized fused Pallas kernel: int8 weights at
        QAT's learned per-layer scales, int8-grid products with int32
        accumulation and fused requantization between layers,
      - "auto"       fused_int8 when trained QAT formats deploy to int8
        AND the BN-folded weights still fit the learned grid; else
        fused_bf16 when every layer's frozen format fits 16 bits; else
        fused_fp32,
  * tile_m selection: an explicit int, or "auto" → the cached autotune
    sweep (core.autotune) keyed on (topology, backend).

An engine is a plain callable `(W,) | (B, W) waveform → symbols`, so it
drops into every site that previously took an `apply_fn`. Engines that
share a `group_key()` (topology + backend + static kernel config) can be
fused into ONE batched launch with per-row weights via
`stacked_engine_fn` — the multi-tenant serving path (repro.serve): batch
row i is computed with engine i's weights, bitwise-identical to engine i
run alone.

All backends share STREAM semantics (one halo pad, VALID convs — see
kernels/cnn_eq/ref.py), so swapping backends never changes results beyond
floating-point fusion noise; the property tests in tests/test_engine.py
assert ≤2-ULP fp32 agreement with the oracle everywhere, bitwise bf16
agreement with the bf16 oracle, and ≤1-LSB int8 agreement with the QAT
fake-quant reference (observed: exact).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune as autotune_lib
from . import qat as qat_lib
from ..kernels.cnn_eq.cnn_eq import table_rows
from .equalizer import (CNNEqConfig, fold_bn, folded_weights, init_bn_state,
                        layer_strides)

BACKENDS = ("ref", "fused_fp32", "fused_bf16", "fused_int8")

Format = Tuple[int, int, int, int]          # (w_int, w_frac, a_int, a_frac)


def _folded_fit_grid(weights, formats) -> bool:
    """True iff every BN-folded weight is representable on its layer's
    learned Q(w_int).(w_frac) grid without saturating. w_int/w_frac may be
    per-output-channel tuples (`qat.per_channel_formats`) — each channel is
    then checked against its own grid."""
    for (w, _), (wi, wf, _, _) in zip(weights, formats):
        wi_col = np.asarray(wi, np.float64).reshape(-1, 1, 1)
        wf_col = np.asarray(wf, np.float64).reshape(-1, 1, 1)
        hi = np.exp2(wi_col) - np.exp2(-wf_col)
        lo = -np.exp2(wi_col)
        wv = np.asarray(w, np.float64)
        if bool(np.any(wv > hi)) or bool(np.any(wv < lo)):
            return False
    return True


@dataclasses.dataclass
class EqualizerEngine:
    """Callable quantized/fused inference engine for the CNN equalizer.

    Build with `EqualizerEngine.from_params` (trained params + BN state,
    QAT formats picked up automatically) or directly from folded weights.
    """
    cfg: CNNEqConfig
    weights: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...]  # BN-folded, fp32
    backend: str = "fused_fp32"
    tile_m: int | str = "auto"
    formats: Optional[Tuple[Format, ...]] = None          # int8 backend only
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.backend == "auto":
            # int8 only when the FOLDED weights still fit the learned grid
            # (see from_params); a vetoed int8 or a 9–16-bit format deploys
            # bf16 — bf16's range covers any learned int width natively.
            if (self._int8_deployable()
                    and _folded_fit_grid(self.weights, self.formats)):
                self.backend = "fused_int8"
            elif self._bf16_deployable():
                self.backend = "fused_bf16"
            else:
                self.backend = "fused_fp32"
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {BACKENDS + ('auto',)}")
        if self.backend == "fused_int8":
            if not self._int8_deployable():
                raise ValueError(
                    "fused_int8 needs per-layer formats that fit int8 "
                    "(qat.deployment_plan(...)['all_int8']); got "
                    f"{self.formats}")
            from ..kernels.cnn_eq.cnn_eq import quantize_weights_int8
            self._qweights = quantize_weights_int8(self.weights, self.formats)
        if self.backend == "fused_bf16":
            from ..kernels.cnn_eq.cnn_eq import cast_weights_bf16
            self._bweights = cast_weights_bf16(self.weights)
        self._strides = layer_strides(self.cfg)
        # the engine pool's WeightTable this engine was last given a slot
        # in (None: never pooled); the table's own slot map is the truth
        self._table: Optional["WeightTable"] = None

    # -- construction ------------------------------------------------------

    @classmethod
    def from_params(cls, params: Dict[str, Any], bn_state: Optional[Dict],
                    cfg: CNNEqConfig, backend: str = "auto",
                    tile_m: int | str = "auto",
                    interpret: Optional[bool] = None,
                    per_channel: bool = False) -> "EqualizerEngine":
        """Deployment step: fold BN, derive quantized-deployment formats
        from learned QAT widths (`qat.deployment_plan`), pick the backend.

        QAT learns Q(w_int) on the UNfolded weights; folding multiplies by
        g = scale/√(var+ε), which can push weights past the learned grid.
        Silently saturating them would break the train→deploy accuracy
        contract, so auto-deployment only goes int8 when the FOLDED weights
        still fit each layer's grid; a vetoed int8 (and any learned format
        in the 9–16-bit range) deploys fused_bf16, whose exponent covers
        the overflow with no clipping; only >16-bit formats (or no QAT at
        all) fall back to fused_fp32.

        per_channel=True refines the learned per-layer weight formats to
        per-output-channel scales (`qat.per_channel_formats`) before the
        backend decision: same learned total width, finer grids on channels
        with small folded weights — no extra arithmetic (the requant is
        already per-row). This is a DEPLOYMENT refinement; the formats are
        derived deterministically from the folded weights, so engine
        rebuilds (e.g. after serve-pool eviction) reproduce them exactly.
        """
        folded = fold_bn(params, bn_state or init_bn_state(cfg), cfg)
        weights = folded_weights(folded)
        formats = None
        if "qat" in params:
            plan = qat_lib.deployment_plan(params["qat"])
            if qat_lib.plan_backend(plan) != "fused_fp32":
                formats = plan["formats"]
        if per_channel and formats is not None:
            formats = qat_lib.per_channel_formats(weights, formats)
        if (backend == "fused_int8" and formats is not None
                and not _folded_fit_grid(weights, formats)):
            raise ValueError(
                "explicit fused_int8 requested but the BN-folded weights "
                "overflow the learned Q(w_int) grids — deploying would "
                "silently saturate; use backend='auto' (deploys bf16) or "
                "retrain with folding-aware QAT")
        return cls(cfg=cfg, weights=weights, backend=backend,
                   tile_m=tile_m, formats=formats, interpret=interpret)

    @classmethod
    def from_folded(cls, folded: Dict[str, Any], cfg: CNNEqConfig,
                    **kw) -> "EqualizerEngine":
        return cls(cfg=cfg, weights=folded_weights(folded), **kw)

    # -- backend plumbing --------------------------------------------------

    def _int8_deployable(self) -> bool:
        return (self.formats is not None
                and all(qat_lib.format_max_bits(wi, wf) <= 8
                        and ai + af + 1 <= 8
                        for wi, wf, ai, af in self.formats))

    def _bf16_deployable(self) -> bool:
        return (self.formats is not None
                and all(max(qat_lib.format_max_bits(wi, wf), ai + af + 1)
                        <= 16
                        for wi, wf, ai, af in self.formats))

    def resolved_tile_m(self) -> int:
        """The tile width actually used (runs the autotune sweep if 'auto')."""
        if isinstance(self.tile_m, int):
            return self.tile_m
        if self.backend == "ref":
            return 64                              # ref has no tiling knob
        best = autotune_lib.best_tile_m(
            self.cfg, self.backend,
            lambda t: self._make_fn(t))
        self.tile_m = best
        return best

    def _make_fn(self, tile_m: int, table=None,
                 slots=None) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """The backend's kernel at `tile_m`: every batch row through this
        engine's weights, or, given a weight table (`table_rows` rows of
        engines of this group) and an int32 slot vector, row i through
        entry slots[i] (fused backends only)."""
        if self.backend == "ref":
            from ..kernels.cnn_eq.ref import cnn_eq as ref_fn
            return functools.partial(ref_fn, weights=self.weights,
                                     strides=self._strides)
        weights = self._layer_weights() if table is None else table
        kw = dict(tile_m=tile_m, interpret=self.interpret, slots=slots)
        if self.backend == "fused_fp32":
            from ..kernels.cnn_eq.cnn_eq import cnn_eq_fused
            return lambda x: cnn_eq_fused(x, weights, self._strides, **kw)
        if self.backend == "fused_bf16":
            from ..kernels.cnn_eq.cnn_eq import cnn_eq_fused_bf16
            return lambda x: cnn_eq_fused_bf16(x, weights, self._strides,
                                               **kw)
        from ..kernels.cnn_eq.cnn_eq import cnn_eq_fused_int8
        return lambda x: cnn_eq_fused_int8(x, weights, self._strides,
                                           self.formats, **kw)

    def _layer_weights(self):
        """The weight pytree the active backend's kernel consumes."""
        if self.backend == "fused_int8":
            return self._qweights
        if self.backend == "fused_bf16":
            return self._bweights
        return self.weights

    # -- the production path -----------------------------------------------

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        """(S·N_os,) or (B, S·N_os) waveform → (S,) or (B, S) soft symbols."""
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None]
        y = self._make_fn(self.resolved_tile_m())(x)
        return y[0] if squeeze else y

    # -- multi-tenant serving surface --------------------------------------

    @property
    def total_stride(self) -> int:
        """Input samples consumed per network pass (V_p · N_os)."""
        n = 1
        for s in self._strides:
            n *= s
        return n

    @property
    def halo_samples(self) -> int:
        """Half a receptive field per side, in SAMPLES — the overlap a
        streaming chunker must carry between chunks."""
        from ..kernels.cnn_eq.ref import receptive_halo
        kernels = tuple(int(w.shape[-1]) for w, _ in self.weights)
        return receptive_halo(kernels, self._strides)

    def tune_key(self) -> Tuple:
        """Hashable (topology, backend, static kernel config) identity —
        the group key WITHOUT the tile width.

        This is the granularity at which the serving layer aggregates
        traffic statistics for serve-aware autotune (`repro.serve`):
        engines that differ only in tile_m share one live width/occupancy
        histogram, and a re-tune picks a new tile FOR this key. Never
        triggers an autotune sweep itself (unlike `group_key`, it does not
        resolve tile_m).
        """
        fmts = self.formats if self.backend == "fused_int8" else None
        return (self.cfg, self.backend, fmts, self.interpret)

    def group_key(self) -> Tuple:
        """Hashable key of everything a batched launch must share.

        Two engines with equal group keys can be stacked into one fused
        launch (`stacked_engine_fn`) — same topology, backend, static
        kernel config (int8 formats are baked into the kernel as requant
        scales) and tile width. Weights are NOT in the key: they ride in
        per-row stacked kernel operands. Structurally this is
        `tune_key() + (tile_m,)`; the serving scheduler relies on that to
        map launches back to their traffic-stats bucket.
        """
        return self.tune_key() + (self.resolved_tile_m(),)

    def describe(self) -> Dict[str, Any]:
        """Deployment summary (for logs / benchmark records)."""
        return {
            "backend": self.backend,
            "tile_m": self.tile_m if isinstance(self.tile_m, int) else "auto",
            "layers": self.cfg.layers,
            "formats": self.formats,
        }


@functools.partial(jax.jit, static_argnames=("backend",))
def _write_rows(table, weights, slot, backend):
    """A new table with entry `slot` set to one engine's rows (no
    donation: the old table stays valid for launches that bound it)."""
    rows = table_rows(weights, backend)
    return jax.tree.map(lambda t, r: t.at[slot].set(r), table, rows)


class WeightTable:
    """Resident per-row weights of engines that share a `tune_key()`, on
    one device, in the fused kernel's operand layout (`table_rows`).

    capacity: weight sets the table holds (its engine pool's
              `max_engines`); `insert` raises RuntimeError past it.
    device:   where the table lives (None: JAX's default device,
              uncommitted, as engine weights are).

    An engine that enters the table gets a free slot, and its rows are
    written there in one jitted update; `free` returns the slot. A write
    makes NEW table arrays and never touches the old ones, so a launch
    that bound a snapshot (`rows`) keeps the weights it was assembled with
    whatever is freed or written afterwards. Thread-safe: slots and the
    current snapshot change together under an internal lock.
    """

    def __init__(self, capacity: int, device=None):
        self.capacity = capacity
        self.device = device
        self._lock = threading.Lock()
        self._arrays = None               # per layer (w, b); first insert
        self._slots: Dict[int, int] = {}  # id(engine) → slot
        self._free = list(range(capacity - 1, -1, -1))

    def insert(self, engine: "EqualizerEngine") -> bool:
        """Give `engine` a slot and write its rows there; False (and no
        write) if it holds one already. The caller keeps the engine alive
        while it is in."""
        weights = jax.device_put(engine._layer_weights(), self.device)
        with self._lock:
            if id(engine) in self._slots:
                return False
            if not self._free:
                raise RuntimeError(
                    f"weight table full ({self.capacity} slots)")
            if self._arrays is None:
                self._arrays = jax.tree.map(
                    lambda r: jax.device_put(np.zeros(
                        (self.capacity,) + r.shape, r.dtype), self.device),
                    jax.eval_shape(functools.partial(
                        table_rows, backend=engine.backend), weights))
            slot = self._free.pop()
            self._arrays = _write_rows(self._arrays, weights,
                                       np.int32(slot), engine.backend)
            self._slots[id(engine)] = slot
        engine._table = self
        return True

    def free(self, engine: "EqualizerEngine") -> None:
        with self._lock:
            slot = self._slots.pop(id(engine), None)
            if slot is not None:
                self._free.append(slot)

    def rows(self, engines) -> Optional[Tuple[Any, np.ndarray]]:
        """(current table snapshot, int32 slot per engine), or None unless
        every engine holds a slot here."""
        with self._lock:
            try:
                slots = [self._slots[id(e)] for e in engines]
            except KeyError:
                return None
            return self._arrays, np.asarray(slots, np.int32)


def stacked_engine_fn(engines, stacking=None
                      ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Fuse same-group engines into ONE batched launch with per-row weights.

    engines: a sequence of `EqualizerEngine`s whose `group_key()`s agree.
    Returns a callable (B, W) → (B, S) where batch row i runs through
    engine i's weights — bitwise-identical to `engines[i](x[i:i+1])` (same
    kernel body, same tile shapes; only the BlockSpec row index differs).
    This is the TPU analogue of the paper's DOP-parallel datapath serving
    many links at once: one kernel grid, many tenants.

    When every engine holds a slot in one `WeightTable` (an engine pool's
    resident table; one engine too), the launch reads that table's
    current snapshot at the engines' slots, and no device work happens
    here. Otherwise one engine runs alone (`e0(x)`), and two or more have
    their rows stacked once into a private table with slots 0…B−1 (eager
    device ops, inside `stacking(B)` when that context factory is given —
    the serving scheduler's `serve.restack` span). Both kinds of table
    run the same program (the backend's kernel with a slot vector) at
    equal table sizes.

    The "ref" backend has no batched-weights kernel (nor a table); it
    falls back to a per-row loop (kept so every backend can be served and
    tested).
    """
    if not engines:
        raise ValueError("stacked_engine_fn needs at least one engine")
    e0 = engines[0]
    key = e0.group_key()
    for e in engines[1:]:
        if e.group_key() != key:
            raise ValueError(
                f"engines are not batch-compatible: {e.group_key()} != {key}")
    table = e0._table
    held = None
    if table is not None and all(e._table is table for e in engines):
        held = table.rows(engines)
    if held is None:
        if len(engines) == 1:
            return lambda x: e0(x)
        if e0.backend == "ref":
            fns = [e._make_fn(e.resolved_tile_m()) for e in engines]
            return lambda x: jnp.concatenate(
                [fn(x[i:i + 1]) for i, fn in enumerate(fns)], axis=0)
        with (stacking(len(engines)) if stacking is not None
              else contextlib.nullcontext()):
            per = [table_rows(e._layer_weights(), e.backend)
                   for e in engines]
            held = (jax.tree.map(lambda *r: jnp.stack(r), *per),
                    np.arange(len(engines), dtype=np.int32))
    return e0._make_fn(e0.resolved_tile_m(), *held)
