"""Device profiler trace: capture around the measured window, and the
reduction from trace to numbers.

The capture writes an XPlane file under a directory of the checkout and
the reduction reads it back with `jax.profiler.ProfileData` (JAX alone).
Device planes are those named `/device:TPU:<n>`; their op-level line
("XLA Ops") holds one event per executed HLO operation, with start and
duration in nanoseconds on the host's clock. The measured window is the
host annotation `bench_window`, written by the benchmark around its
window, so that device time outside it (set-up, reference) is left out.

`extract` turns a trace into plain data (host spans, device ops), which
`reduce` turns into numbers; `bench/testdata` keeps such an extract of a
chip trace for the tests. Per device the reduction gives:
  * busy  — the union of op intervals inside the window;
  * idle  — the window less busy;
  * kernel — summed durations of the ops that match the kernel pattern;
  * glue  — summed durations of every other op;
and over the devices the ten ops that took most time and the ten longest
idle gaps, each named by what the host was doing at its midpoint (the
innermost host annotation, or a span the driver supplies, that covers it).
"""
from __future__ import annotations

import collections
import contextlib
import glob
import gzip
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
# The fused equalizer is a Mosaic (Pallas) kernel: XLA launches it as a
# custom call whose target, `tpu_custom_call`, the op's name in the trace
# spells out (`... custom-call(...), custom_call_target="tpu_custom_call"`).
# Matching the target, not the kernel body's function name, survives a
# rename of the body.
KERNEL_PATTERN = r"tpu_custom_call"

Interval = Tuple[int, int]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into `log_dir` (device ops and host
    annotations; no Python call tracing)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def load(path: str):
    """ProfileData of an .xplane.pb file, gzipped or not."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def host_spans(pd) -> List[Tuple[str, int, int]]:
    """(name, start_ns, end_ns) of every event on the host planes."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def extract(pd) -> Dict:
    """What the reduction reads from a trace, as plain data: the host
    spans, and per device plane the (op name, start_ns, end_ns) of its op
    line. Op names carry the HLO instruction, a custom call's target
    included."""
    devices = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name == OP_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((ev.name, s, s + int(ev.duration_ns)))
        devices[plane.name] = evs
    return {"host": host_spans(pd), "devices": devices}


def window(ex: Dict, name: str = WINDOW) -> Interval:
    spans = [(s, e) for n, s, e in ex["host"] if n == name]
    if not spans:
        raise RuntimeError(f"no host annotation {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi)."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(t: int, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost span covering t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


_OPCODE = re.compile(r" ([\w\-]+)\(")


def _short(op: str) -> str:
    """An op's HLO instruction name, result type and opcode, without its
    layout and operands: `%fusion f32[1199104] fusion`."""
    name, _, rest = op.partition(" = ")
    if not rest:
        return op[:120]
    kind = "tuple" if rest.startswith("(") else rest.split("{")[0].split()[0]
    m = _OPCODE.search(rest)
    return f"{name} {kind} {m.group(1) if m else ''}".strip()


def reduce(ex: Dict, extra_spans: Sequence[Tuple[str, int, int]] = (),
           top: int = 10) -> Dict:
    """The numbers of a traced window (see module docstring), from
    `extract`'s data."""
    lo, hi = window(ex)
    if not ex["devices"]:
        raise RuntimeError("no device plane with an op line in the trace")
    pat = re.compile(KERNEL_PATTERN)
    spans = [sp for sp in ex["host"] if sp[0] != WINDOW]
    spans += list(extra_spans)
    devices, totals = [], collections.Counter()
    idle: List[Tuple[int, int]] = []
    for plane, evs in sorted(ex["devices"].items()):
        inside = [(n, max(s, lo), min(e, hi), bool(pat.search(n)))
                  for n, s, e in evs if e > lo and s < hi]
        busy = union(((s, e) for _, s, e, _ in inside), lo, hi)
        kernel = sum(e - s for _, s, e, k in inside if k)
        glue = sum(e - s for _, s, e, k in inside if not k)
        for n, s, e, _ in inside:
            totals[_short(n)] += e - s
        idle.extend(gaps(busy, lo, hi))
        devices.append({
            "plane": plane,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "kernel_s": kernel * 1e-9,
            "kernel_events": sum(1 for *_, k in inside if k),
            "glue_s": glue * 1e-9,
        })
    idle.sort(key=lambda g: g[0] - g[1])
    longest = [(_label((s + e) // 2, spans), e - s) for s, e in idle[:top]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "kernel_s": sum(d["kernel_s"] for d in devices),
        "glue_s": sum(d["glue_s"] for d in devices),
        "kernel_events": sum(d["kernel_events"] for d in devices),
        "top_ops": [[n, t * 1e-9] for n, t in totals.most_common(top)],
        "idle_gaps": [[n, t * 1e-9] for n, t in longest],
    }


def remove(log_dir: Optional[str]) -> None:
    if log_dir:
        shutil.rmtree(log_dir, ignore_errors=True)
