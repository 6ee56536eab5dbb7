"""tile_m autotuning for the fused equalizer kernels.

The paper's DOP knob (how many MACs the FPGA instantiates per layer) maps on
TPU to the fused kernel's sequence-tile width `tile_m`: it sets how much of
the vector unit's 128-lane axis each tap's products fill and how well the
tile DMAs overlap compute. The best value depends on the topology (receptive field →
halo overhead per tile) and on the backend (int8 tiles fit 4× more VMEM),
so DOP-style operating points (`equalizer_ht`, `equalizer_lp`) each get
their own sweep.

Results are cached twice:
  * in-process, keyed on (CNNEqConfig, backend, width-bucket), and
  * on disk (reports/autotune_tile_m.json), so benchmark runs and future
    sessions skip the sweep entirely.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from .equalizer import CNNEqConfig

DEFAULT_TILES: Tuple[int, ...] = (16, 32, 64, 128, 256)
CACHE_PATH = (pathlib.Path(__file__).resolve().parents[3]
              / "reports" / "autotune_tile_m.json")

_memory_cache: Dict[Tuple, int] = {}


def cache_key(cfg: CNNEqConfig, backend: str) -> Tuple:
    # the device kind is part of the key: an interpret-mode sweep on a CPU
    # host must not pin the tile choice for TPU silicon, nor a v5e sweep
    # the tile of another chip
    kind = jax.devices()[0].device_kind.replace(" ", "_")
    return (cfg.layers, cfg.kernel, cfg.channels, cfg.v_parallel, cfg.n_os,
            backend, kind)


def _key_str(key: Tuple) -> str:
    l, k, c, vp, nos, backend, kind = key[:7]
    s = f"L{l}_K{k}_C{c}_Vp{vp}_Nos{nos}__{backend}__{kind}"
    if len(key) > 7:                   # batched-serving sweep (probe_batch>1)
        s += f"__B{key[7]}"
    if len(key) > 8:                   # serve-aware sweep: live-traffic width
        s += f"_S{key[8]}"
    return s


def _load_disk() -> Dict[str, int]:
    try:
        return json.loads(CACHE_PATH.read_text())
    except (OSError, ValueError):
        return {}


def _store_disk(key: Tuple, tile_m: int) -> None:
    data = _load_disk()
    data[_key_str(key)] = tile_m
    try:
        CACHE_PATH.parent.mkdir(parents=True, exist_ok=True)
        CACHE_PATH.write_text(json.dumps(data, indent=2, sort_keys=True))
    except OSError:
        pass                       # read-only checkout: in-memory cache only


def time_callable(fn: Callable[[jnp.ndarray], jnp.ndarray], x: jnp.ndarray,
                  iters: int = 3) -> float:
    """Mean seconds per call, compiling outside the timed region — the one
    timing methodology shared by the autotuner and the engine benchmarks."""
    y = fn(x)
    jax.block_until_ready(y)       # warm-up: compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(x))
    return (time.perf_counter() - t0) / iters


def best_tile_m(cfg: CNNEqConfig, backend: str,
                make_fn: Callable[[int], Callable[[jnp.ndarray], jnp.ndarray]],
                candidates: Optional[Iterable[int]] = None,
                probe_syms: int = 4096,
                use_disk: bool = True,
                probe_batch: int = 1) -> int:
    """Sweep tile_m candidates for (cfg, backend); return the fastest.

    make_fn(tile_m) must return a jit-able callable (B, W) → (B, S). The
    probe input is `probe_batch` rows of `probe_syms` symbols — long enough
    that every candidate runs multiple grid tiles. probe_batch > 1 models
    the multi-tenant serving shape (repro.serve stacks B tenant chunks per
    launch) and gets its own cache slot, keyed on BOTH the batch and the
    probe width — the best tile for one long stream is not necessarily best
    when B rows split VMEM, and the serve-aware re-tune
    (`repro.serve.runtime` `_serve_tile`) probes with the width observed in
    live traffic rather than the default.
    """
    if candidates is None:
        candidates = DEFAULT_TILES       # resolved at call time (testable)
    key = cache_key(cfg, backend)
    if probe_batch != 1:
        key = key + (probe_batch, probe_syms)
    if key in _memory_cache:
        return _memory_cache[key]
    if use_disk:
        hit = _load_disk().get(_key_str(key))
        if hit is not None:
            _memory_cache[key] = int(hit)
            return int(hit)

    x = jax.random.normal(jax.random.PRNGKey(0),
                          (probe_batch, probe_syms * cfg.n_os), jnp.float32)
    timings: Dict[int, float] = {}
    for tile_m in candidates:
        timings[int(tile_m)] = time_callable(make_fn(int(tile_m)), x)
    best = min(timings, key=timings.get)
    _memory_cache[key] = best
    if use_disk:
        _store_disk(key, best)
    return best


def clear_cache(disk: bool = False) -> None:
    _memory_cache.clear()
    if disk:
        try:
            CACHE_PATH.unlink()
        except OSError:
            pass
