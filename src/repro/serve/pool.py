"""Keyed, LRU-bounded engine pool — the session manager's memory bound.

Millions of tenants cannot all keep a live `EqualizerEngine` (folded fp32
weights + backend-specific quantized copies) resident. The pool holds at
most `max_engines` built engines, keyed by tenant identity; a hit refreshes
recency, a miss builds via the caller-supplied factory and evicts the least
recently used entry. Evicting an engine loses NO stream state — chunker
carries live in the `Session`, and the factory rebuilds the engine
deterministically from the tenant's spec (BN folding and weight
quantization are pure functions of the trained params).

The pool also keeps the weights of its fused-backend engines resident on
the device: one `WeightTable` per `tune_key()` (capacity `max_engines`,
on the pool's `device`), which stacked launches index per row
(`core.engine.stacked_engine_fn`). An engine entering the pool (build,
swap, failover rebuild) has its rows written to a free slot; eviction,
`drop` and `clear` free the slot.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional

from ..core.engine import EqualizerEngine, WeightTable
from ..obs.trace import annotate


class EnginePool:
    """LRU cache of built engines: key → engine.

    max_engines: resident-engine bound (count; default 32; must be ≥ 1 or
                 __init__ raises ValueError). Sizing note: one engine holds
                 folded fp32 weights plus a backend-specific quantized copy
                 (int8/bf16), so the bound is effectively a host-memory
                 knob. A bound smaller than the number of concurrently
                 ACTIVE tenants still works — engines rebuild on demand —
                 but turns steady-state traffic into rebuild churn
                 (`stats()["evictions"]` is the tell).

    device:      where the pool's weight tables live (default None: JAX's
                 default device). A fleet worker passes its own device, so
                 its launches read local weights.

    Thread-safety: every operation is atomic under an internal lock — the
    async serving threads touch the pool under the runtime lock, but the
    online-adaptation thread (`repro.adapt`) reads engines outside it, so
    the pool must not rely on its callers for consistency. `get` builds on
    a miss OUTSIDE the lock (engine construction is pure but slow —
    BN fold, weight quantization, possibly an autotune sweep); two racing
    misses may both build, and the second build wins the slot — benign,
    deterministic engines are interchangeable.
    """

    def __init__(self, max_engines: int = 32, device=None):
        if max_engines < 1:
            raise ValueError("max_engines must be ≥ 1")
        self.max_engines = max_engines
        self.device = device
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._tables: Dict[Hashable, WeightTable] = {}   # tune_key → table
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.table_writes = 0
        # optional chaos hook (serve/recovery.py FaultPlan): `build_error`
        # faults are scheduled against the miss/build counter, so they hit
        # both session opens AND failover rebuilds deterministically
        self.fault_plan = None
        # optional observability hook (repro.obs): called as
        # build_hook(key, build_seconds) after every successful miss-build,
        # outside the pool lock — runtimes use it to record engine
        # build/compile events as trace instants + a build-time histogram
        self.build_hook: Optional[Callable[[Hashable, float], None]] = None
        self.clock: Callable[[], float] = time.perf_counter

    def get(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the cached engine for `key`, building (and possibly
        evicting the LRU entry) on a miss. An installed `fault_plan` may
        fail the build at its scheduled build index — the exception
        propagates to the caller exactly like a real build failure."""
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]
            idx = self.misses
            self.misses += 1
        if self.fault_plan is not None:
            self.fault_plan.on_build(idx)
        t0 = self.clock()
        with annotate("serve.engine_build"):
            engine = build()               # slow: outside the lock
        if self.build_hook is not None:
            self.build_hook(key, self.clock() - t0)
        with self._lock:
            if key in self._entries:            # a racing build won first
                self._release(self._entries.pop(key))
            elif len(self._entries) >= self.max_engines:
                self._release(self._entries.popitem(last=False)[1])  # LRU
                self.evictions += 1
            self._entries[key] = engine
            self._hold(engine)
        return engine

    def _hold(self, engine: Any) -> None:
        """Write a fused-backend engine's rows into its table (lock held)."""
        if not isinstance(engine, EqualizerEngine) or engine.backend == "ref":
            return
        key = engine.tune_key()
        if key not in self._tables:
            self._tables[key] = WeightTable(self.max_engines, self.device)
        if self._tables[key].insert(engine):
            self.table_writes += 1

    def _release(self, engine: Any) -> None:
        """Free the engine's table slot, if it holds one here (lock held)."""
        table = getattr(engine, "_table", None)
        if table is not None and table in self._tables.values():
            table.free(engine)

    def __contains__(self, key: Hashable) -> bool:     # no recency touch
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def drop(self, key: Hashable) -> None:
        with self._lock:
            if key in self._entries:
                self._release(self._entries.pop(key))

    def clear(self) -> None:
        """Drop every entry (fleet worker death: the dead device's built
        engines are garbage; sessions rebuild on their new worker's pool).
        Hit/miss/eviction counters are preserved — they describe history,
        not contents."""
        with self._lock:
            for engine in self._entries.values():
                self._release(engine)
            self._entries.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"size": len(self._entries),
                    "max_engines": self.max_engines,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "table_writes": self.table_writes}
