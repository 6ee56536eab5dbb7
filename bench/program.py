"""The system under test, as the benchmark builds it from a configuration
file. This is the one module that imports the program (`repro`).

Engines are built from the benchmark's own weights and the configuration's
fixed-point formats, which pick the backend through the engine's own
deployment ladder ("auto"); a backend other than the configuration's is an
error. The tile width is the configuration's, so no autotune sweep runs in
set-up.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from .harness import BenchError


def cnn_config(topo: Dict):
    from repro.core.equalizer import CNNEqConfig
    return CNNEqConfig(**topo)


def formats(config: Dict) -> Tuple[Tuple[int, int, int, int], ...]:
    return tuple(tuple(int(v) for v in f) for f in config["formats"])


def engine(config: Dict, weights) -> object:
    """One `EqualizerEngine` with the given (w, b) layers."""
    from repro.core.engine import EqualizerEngine
    eng = EqualizerEngine(cfg=cnn_config(config["topology"]),
                          weights=tuple(weights), backend="auto",
                          tile_m=int(config["tile_m"]),
                          formats=formats(config))
    if eng.backend != config["backend"]:
        raise BenchError(f"formats deployed {eng.backend}, the "
                         f"configuration states {config['backend']}")
    return eng


def tenant_spec(config: Dict, tenant_id: str, weights):
    from repro.serve import TenantSpec
    return TenantSpec(tenant_id, cnn_config(config["topology"]),
                      weights=tuple(weights), formats=formats(config),
                      backend="auto", tile_m=int(config["tile_m"]))


def partitioned_step(config: Dict, eng):
    """The HT deployment unit as one jitted program: split with the §6.1
    overlap, equalize every instance, drop the overlap and merge."""
    import jax
    from repro.core import stream_partition as sp
    n_inst = int(config["deployment"]["n_instances"])
    cfg = cnn_config(config["topology"])
    return jax.jit(lambda x: sp.partitioned_apply(eng, x, n_inst, cfg))


def runtime(config: Dict, devices: Sequence, obs):
    """AsyncServeRuntime on one chip; a FleetRuntime with one worker per
    chip on more. The pool holds every tenant of a chip."""
    from repro.serve import AsyncServeRuntime, BatchPolicy, FleetRuntime
    dep = config["deployment"]
    policy = BatchPolicy(**dep["policy"])
    per_chip = int(dep["tenants_per_chip"])
    if len(devices) == 1:
        return AsyncServeRuntime(policy, max_engines=per_chip, obs=obs)
    return FleetRuntime(n_workers=len(devices), devices=list(devices),
                        policy=policy, max_engines=per_chip, obs=obs)


def observability(tracing: bool, capacity: int):
    """A hub whose tracer keeps `capacity` chunk spans, for a traced run;
    None (the runtime's own default hub, tracing off) otherwise."""
    if not tracing:
        return None
    from repro.obs import Observability
    from repro.obs.hub import Retention
    return Observability(tracing=True,
                         retention=Retention(trace_capacity=capacity))


def stacked_fn(engines: List):
    from repro.core.engine import stacked_engine_fn
    return stacked_engine_fn(engines)


def batchers(rt) -> List:
    """The runtime's micro-batchers: one, or one per fleet worker."""
    if hasattr(rt, "workers"):
        return [w.batcher for w in rt.workers]
    return [rt.batcher]


def engines_by_device(rt) -> List[Tuple[object, List]]:
    """[(device or None, engines of the tenants served there)]."""
    if hasattr(rt, "workers"):
        sessions = rt.sessions
        homes = rt.stats()["placement"]
        return [(w.device, [s.engine for tid, s in sorted(sessions.items())
                            if homes[tid] == w.idx])
                for w in rt.workers]
    return [(None, [s.engine for _, s in
                    sorted(rt.sessions.sessions.items())])]


def spans(obs) -> List:
    """The sealed chunk spans of a traced runtime."""
    return obs.tracer.sealed_spans()
