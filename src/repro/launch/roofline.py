"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Hardware constants come from `PEAKS`, a table of published per-chip peaks
keyed by `jax.Device.device_kind`; a device that is not in it is an error.

XLA's `cost_analysis()` visits while-loop bodies ONCE, so a scan-over-layers
model under-counts by L× (and grad accumulation by accum×). This module
therefore carries its own small HLO analyzer:

  * parses the per-partition post-optimization HLO text into computations /
    instructions (a symbol table resolves operand shapes — post-fusion HLO
    prints operands as bare names);
  * extracts `known_trip_count` from every `while` and composes NESTED loop
    multipliers (accum loop × layer scan);
  * FLOPs: 2·numel(result)·K for every dot (K = lhs contracting dims), ×mult;
  * HBM bytes: Σ (operand + result bytes) over top-level instructions of
    reachable computations (entry + while bodies) — fusion-internal traffic
    excluded, which is exactly the fusion memory model;
  * collective traffic: operand sizes per op kind ×mult, plus a ring-model
    per-chip bytes-moved estimate.

Terms (seconds, per step, per chip):
  compute    = flops / peak bf16 FLOP/s
  memory     = hbm_bytes / peak HBM bytes/s
  collective = ring_bytes / ICI bytes/s per link
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# Published per-chip peaks, keyed by `device_kind`. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links, 50 GB/s
# each).
V5E = "TPU v5 lite"
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"bf16_flops": 197e12, "int8_ops": 393e12,
          "hbm_bytes_per_s": 819e9, "ici_link_bytes_per_s": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of `device_kind`; raises for a
    device that is not in `PEAKS` rather than assuming another's."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|s4|u4|s8|u8|s16|u16|f16|bf16|s32|u32|f32"
                       r"|s64|u64|f64|c64|c128)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w\.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"^(\(?.*?\)?)\s([\w\-]+)\(")
# computation headers sit at column 0 and end with "{":
#   %region_2.2_spmd (param: (s32[], …)) -> (…) {
#   ENTRY %main.1234 (…) -> (…) {
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w\.\-]+)\s*\(")
_OPERAND_RE = re.compile(r"%[\w\.\-]+")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_GROUPS_BRACES_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
# ops that move no HBM data (views / metadata / control)
_NO_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "conditional", "call", "after-all",
               "partition-id", "replica-id", "iota", "get-dimension-size"}


def _numel(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _shape_bytes(dtype: str, dims: str) -> int:
    return _numel(dims) * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)          # replica_groups=[G,S]<=[...]
    if m:
        return max(int(m.group(2)), 2)
    m = _GROUPS_BRACES_RE.search(line)        # replica_groups={{0,1,…},…}
    if m:
        return max(len(m.group(1).split(",")), 2)
    return 2


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    result_bytes: int
    result_dims: Optional[List[int]]
    operands: List[str]
    line: str
    comp: str


@dataclasses.dataclass
class CollectiveStats:
    op_bytes: Dict[str, int]          # op kind → Σ operand bytes (per chip)
    ring_bytes: Dict[str, float]      # op kind → ring-model bytes moved/chip
    count: Dict[str, int]

    @property
    def total_operand_bytes(self) -> int:
        return sum(self.op_bytes.values())

    @property
    def total_ring_bytes(self) -> float:
        return sum(self.ring_bytes.values())


@dataclasses.dataclass
class HloAnalysis:
    flops: float                      # per-chip dot flops (loop-scaled)
    hbm_bytes: float                  # per-chip fusion-level traffic
    coll: CollectiveStats
    xla_flops: float = 0.0            # cost_analysis (loops counted once)
    xla_bytes: float = 0.0
    top_traffic: Optional[list] = None    # [(bytes, opcode, op_name), …]
    top_collectives: Optional[list] = None


_METADATA_RE = re.compile(r'op_name="([^"]*)"')


def analyze_hlo(text: str, top_k: int = 0) -> HloAnalysis:
    comp = ""
    instrs: List[_Instr] = []
    sym_bytes: Dict[str, int] = {}
    sym_dims: Dict[str, Optional[List[int]]] = {}
    whiles: List[Tuple[str, str, str, int]] = []   # (comp, body, cond, trip)

    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cm = _COMP_RE.match(line)
            if cm and " = " not in line.split("->")[0]:
                comp = cm.group(1).lstrip("%")
                continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1).lstrip("%"), m.group(2)
        om = _OP_RE.match(rest)
        if not om:
            continue
        result_str, opcode = om.group(1), om.group(2)
        shapes = _SHAPE_RE.findall(result_str)
        rbytes = sum(_shape_bytes(d, s) for d, s in shapes)
        rdims = ([int(x) for x in shapes[0][1].split(",") if x]
                 if len(shapes) == 1 else None)
        sym_bytes[name] = rbytes
        sym_dims[name] = rdims
        paren = rest[om.end() - 1:]
        operand_str = paren[1:paren.find(")")] if ")" in paren else ""
        operands = [o.lstrip("%") for o in _OPERAND_RE.findall(operand_str)]
        instrs.append(_Instr(name, opcode, rbytes, rdims, operands, line,
                             comp))
        if opcode == "while":
            b = _BODY_RE.search(line)
            c = _COND_RE.search(line)
            t = _TRIP_RE.search(line)
            whiles.append((comp, b.group(1) if b else "",
                           c.group(1) if c else "",
                           int(t.group(1)) if t else 1))

    # loop multipliers (compose nested loops via fixpoint)
    mult: Dict[str, float] = {}
    entry_comps = {i.comp for i in instrs}
    bodies = {b for _, b, _, _ in whiles} | {c for _, _, c, _ in whiles}
    for c in entry_comps - bodies:
        mult[c] = 1.0
    for _ in range(12):
        changed = False
        for parent, body, cond, trip in whiles:
            if parent in mult:
                for target, t in ((body, trip), (cond, trip + 1)):
                    val = mult[parent] * max(t, 1)
                    if target and mult.get(target) != val:
                        mult[target] = val
                        changed = True
        if not changed:
            break
    reachable = set(mult)

    flops = 0.0
    hbm = 0.0
    op_bytes: Dict[str, int] = {}
    ring: Dict[str, float] = {}
    count: Dict[str, int] = {}
    contributors: list = []
    coll_contrib: list = []

    for ins in instrs:
        if ins.comp not in reachable:
            continue                     # fusion bodies / reducers
        m = mult.get(ins.comp, 1.0)
        base = ins.opcode.replace("-start", "").replace("-done", "")
        operand_bytes = sum(sym_bytes.get(o, 0) for o in ins.operands)

        if ins.opcode == "dot" and ins.result_dims is not None:
            lc = _LHS_CONTRACT_RE.search(ins.line)
            k = 1
            lhs_dims = sym_dims.get(ins.operands[0]) if ins.operands else None
            if lc and lhs_dims:
                for idx in lc.group(1).split(","):
                    if idx:
                        k *= lhs_dims[int(idx)]
            flops += 2.0 * _numel(",".join(map(str, ins.result_dims))) \
                * k * m
        elif ins.opcode == "convolution" and ins.result_dims is not None:
            # 2 · numel(out) · (K_spatial · C_in): operand1 = kernel
            kdims = sym_dims.get(ins.operands[1]) if len(ins.operands) > 1 \
                else None
            kprod = 1
            if kdims:
                for d in kdims[:-1]:     # all but output-feature dim
                    kprod *= d
            n_out = 1
            for d in ins.result_dims:
                n_out *= d
            flops += 2.0 * n_out * kprod * m

        if base in _COLL_OPS and not ins.opcode.endswith("-done"):
            n = _group_size(ins.line)
            op_bytes[base] = op_bytes.get(base, 0) + int(operand_bytes * m)
            count[base] = count.get(base, 0) + int(m)
            if base == "all-gather":
                moved = operand_bytes * (n - 1)
            elif base == "all-reduce":
                moved = 2.0 * operand_bytes * (n - 1) / n
            elif base in ("reduce-scatter", "all-to-all"):
                moved = operand_bytes * (n - 1) / n
            else:                        # collective-permute
                moved = operand_bytes
            ring[base] = ring.get(base, 0.0) + moved * m

        if base in _COLL_OPS and top_k and not ins.opcode.endswith("-done"):
            meta = _METADATA_RE.search(ins.line)
            coll_contrib.append((operand_bytes * m, base,
                                 meta.group(1)[-90:] if meta else ins.name))

        if ins.opcode in _NO_TRAFFIC or ins.opcode.endswith("-done"):
            continue
        traffic = (operand_bytes + ins.result_bytes) * m
        hbm += traffic
        if top_k:
            meta = _METADATA_RE.search(ins.line)
            contributors.append((traffic, ins.opcode,
                                 meta.group(1)[-90:] if meta else ins.name))

    contributors.sort(reverse=True)
    coll_contrib.sort(reverse=True)
    return HloAnalysis(flops=flops, hbm_bytes=hbm,
                       coll=CollectiveStats(op_bytes, ring, count),
                       top_traffic=contributors[:top_k] or None,
                       top_collectives=coll_contrib[:top_k] or None)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    return analyze_hlo(hlo_text).coll


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-chip flops (loop-scaled dot flops)
    hbm_bytes: float             # per-chip bytes accessed
    coll: CollectiveStats
    n_chips: int
    model_flops: float = 0.0     # 6·N·D (global, useful work)
    xla_flops: float = 0.0
    xla_bytes: float = 0.0
    device_kind: str = V5E       # the chip the dry run was compiled for

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind)["bf16_flops"]

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / peaks(self.device_kind)["hbm_bytes_per_s"]

    @property
    def t_collective(self) -> float:
        return (self.coll.total_ring_bytes
                / peaks(self.device_kind)["ici_link_bytes_per_s"])

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_step(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (per-chip HLO flops × chips) — remat/pad waste."""
        total = self.flops * self.n_chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline step time."""
        total = (self.n_chips * peaks(self.device_kind)["bf16_flops"]
                 * self.t_step)
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "xla_flops_per_chip_loops_once": self.xla_flops,
            "xla_bytes_per_chip_loops_once": self.xla_bytes,
            "collective_operand_bytes": self.coll.total_operand_bytes,
            "collective_ring_bytes": self.coll.total_ring_bytes,
            "collective_ops": self.coll.count,
            "collective_ring_bytes_by_op": self.coll.ring_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "t_step_s": self.t_step,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_at_roofline": self.mfu,
        }


def model_flops(n_active_params: int, tokens: int, kind: str) -> float:
    """6·N·D for training, 2·N·D for inference forward passes."""
    factor = 6.0 if kind == "train" else 2.0
    return factor * n_active_params * tokens


def from_compiled(compiled, n_chips: int, model_fl: float = 0.0,
                  hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):           # older jax returns [dict]
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    an = analyze_hlo(text)
    return Roofline(flops=an.flops, hbm_bytes=an.hbm_bytes, coll=an.coll,
                    n_chips=n_chips, model_flops=model_fl,
                    xla_flops=xla_flops, xla_bytes=xla_bytes)
