"""Trip-count-corrected statistics from optimized HLO text.

XLA's `compiled.cost_analysis()` counts a `while` body ONCE, not
times-trip-count — for scan-over-layers models that undercounts FLOPs,
bytes and collective traffic by ~L (and by the microbatch count, and by
inner attention/WKV chunk scans).  tests/test_hlo_stats.py demonstrates the
raw undercount and validates this module's correction.

This analyzer parses the optimized HLO, builds the computation graph, and
aggregates per-computation statistics recursively, multiplying `while`
bodies by their `known_trip_count` backend config (emitted by XLA whenever
the trip count is static — always true for lax.scan):

  * matmul FLOPs: every `dot` op — 2 * prod(result) * prod(contracted)
  * elementwise/reduce FLOPs: 1 flop per output (inputs for reductions)
  * HBM bytes: per top-level op, operand bytes + result bytes.  Optimized
    HLO is mostly fusions; a fusion's operands/results ARE its HBM traffic
    (internal reuse stays in registers/VMEM), so this is the right
    granularity.  Fusion bodies are descended only for FLOPs.
  * collective bytes: result sizes of all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute, per kind.

All numbers are per-device (the partitioned module is the per-device
program).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["HloStats", "analyze_hlo"]


DTYPE_BYTES = {
    "pred": 0.125, "s2": 0.25, "u2": 0.25, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")
_OP_LINE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([\w\-]+)\("
)
_TRIP = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"?(\d+)')
_CALLED = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "ragged-all-to-all",
}
ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs", "sign",
    "compare", "select", "and", "or", "xor", "not", "clamp", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "cosine", "sine", "logistic",
    "exponential-minus-one", "log-plus-one", "atan2", "remainder", "cbrt",
    "erf",
}
SKIP_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "while", "conditional",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "async-done", "async-start", "async-update", "get-dimension-size",
}


def _type_bytes(type_str: str) -> int:
    total = 0.0
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return int(total)


def _type_numel(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype in ("token", "opaque"):
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _first_shape_dims(type_str: str) -> List[int]:
    m = _SHAPE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class _Op:
    name: str
    type_str: str
    opcode: str
    rest: str  # remainder of the line after the opcode's "("


@dataclasses.dataclass
class _Computation:
    name: str
    ops: List[_Op] = dataclasses.field(default_factory=list)
    symbols: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    matmul_flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    collective_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    collective_counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    unknown_trip_whiles: int = 0

    def add(self, other: "HloStats", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.matmul_flops += other.matmul_flops * mult
        self.bytes_accessed += other.bytes_accessed * mult
        self.collective_bytes += other.collective_bytes * mult
        for k, v in other.collective_by_kind.items():
            self.collective_by_kind[k] = self.collective_by_kind.get(k, 0.0) + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0.0) + v * mult
        self.unknown_trip_whiles += other.unknown_trip_whiles

    def as_dict(self) -> Dict:
        return {
            "flops": self.flops,
            "matmul_flops": self.matmul_flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "collective_by_kind": dict(self.collective_by_kind),
            "collective_counts": dict(self.collective_counts),
            "unknown_trip_whiles": self.unknown_trip_whiles,
        }


def _parse_computations(text: str) -> Tuple[Dict[str, _Computation], Optional[str]]:
    comps: Dict[str, _Computation] = {}
    entry: Optional[str] = None
    current: Optional[_Computation] = None
    for line in text.splitlines():
        if current is None:
            m = _COMP_HEADER.match(line)
            if m:
                current = _Computation(m.group(1))
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if line.startswith("}"):
            comps[current.name] = current
            current = None
            continue
        m = _OP_LINE.match(line)
        if m:
            name, type_str, opcode = m.group(1), m.group(2), m.group(3)
            rest = line[m.end():]
            current.ops.append(_Op(name, type_str, opcode, rest))
            current.symbols[name] = type_str
    if current is not None:
        comps[current.name] = current
    return comps, entry


def _dot_flops(op: _Op, comp: _Computation, comps: Dict[str, _Computation]) -> float:
    """2 * prod(result dims) * prod(lhs contracting dims)."""
    result = _first_shape_dims(op.type_str)
    operands = _OPERAND.findall(op.rest)
    m = _CONTRACT.search(op.rest)
    if not operands:
        return 0.0
    lhs_type = comp.symbols.get(operands[0])
    if lhs_type is None:
        for c in comps.values():
            if operands[0] in c.symbols:
                lhs_type = c.symbols[operands[0]]
                break
    if lhs_type is None:
        return 2.0 * max(_type_numel(op.type_str), 1)
    lhs_dims = _first_shape_dims(lhs_type)
    contract = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            di = int(d)
            if di < len(lhs_dims):
                contract *= lhs_dims[di]
    res = 1
    for d in result:
        res *= d
    return 2.0 * res * contract


def _analyze_comp(
    name: str,
    comps: Dict[str, _Computation],
    cache: Dict[Tuple[str, bool], HloStats],
    stack: Tuple[str, ...] = (),
    *,
    count_bytes: bool = True,
) -> HloStats:
    key = (name, count_bytes)
    if key in cache:
        return cache[key]
    if name in stack or name not in comps:
        return HloStats()
    comp = comps[name]
    stats = HloStats()
    for op in comp.ops:
        oc = op.opcode
        if oc == "while":
            m = _TRIP.search(op.rest)
            trip = float(m.group(1)) if m else 1.0
            if not m:
                stats.unknown_trip_whiles += 1
            called = _CALLED.search(op.rest)
            if called:
                body = _analyze_comp(
                    called.group(1), comps, cache, stack + (name,),
                    count_bytes=count_bytes,
                )
                stats.add(body, trip)
            continue
        if oc == "conditional":
            m = _BRANCHES.search(op.rest)
            branches = []
            if m:
                branches = [b.strip().lstrip("%") for b in m.group(1).split(",")]
            else:
                branches = _CALLED.findall(op.rest)
            if branches:
                subs = [
                    _analyze_comp(b, comps, cache, stack + (name,), count_bytes=count_bytes)
                    for b in branches
                ]
                best = max(subs, key=lambda s: s.flops + s.bytes_accessed)
                stats.add(best)
            continue
        if oc in ("call", "fusion", "async-start"):
            called = _CALLED.search(op.rest)
            if called:
                # Descend for FLOPs only; fusion HBM traffic is its
                # top-level operands + result, counted below.
                sub = _analyze_comp(
                    called.group(1), comps, cache, stack + (name,), count_bytes=False
                )
                stats.flops += sub.flops
                stats.matmul_flops += sub.matmul_flops
                stats.collective_bytes += sub.collective_bytes
                for k, v in sub.collective_by_kind.items():
                    stats.collective_by_kind[k] = stats.collective_by_kind.get(k, 0) + v
        if oc == "dot":
            f = _dot_flops(op, comp, comps)
            stats.flops += f
            stats.matmul_flops += f
        elif oc in ELEMENTWISE:
            stats.flops += _type_numel(op.type_str)
        elif oc in ("reduce", "reduce-window"):
            operands = _OPERAND.findall(op.rest)
            if operands and operands[0] in comp.symbols:
                stats.flops += _type_numel(comp.symbols[operands[0]])
            else:
                stats.flops += _type_numel(op.type_str)
        elif oc == "convolution":
            # No conv-using arch in the zoo (frontends stubbed); coarse count.
            stats.flops += 2.0 * _type_numel(op.type_str)

        base = oc.replace("-start", "")
        if base in {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute", "ragged-all-to-all"}:
            b = _type_bytes(op.type_str)
            stats.collective_bytes += b
            stats.collective_by_kind[base] = stats.collective_by_kind.get(base, 0.0) + b
            stats.collective_counts[base] = stats.collective_counts.get(base, 0.0) + 1

        if count_bytes and oc not in SKIP_BYTES:
            if oc in ("dynamic-slice", "gather"):
                # Reads only the selected window, not the whole operand.
                b = 2 * _type_bytes(op.type_str)
            elif oc in ("dynamic-update-slice", "scatter"):
                # In-place (XLA aliases the buffer): traffic ~ the update
                # operand read+write, not the full result buffer.
                operands = _OPERAND.findall(op.rest)
                upd = comp.symbols.get(operands[1]) if len(operands) > 1 else None
                b = 2 * _type_bytes(upd) if upd else _type_bytes(op.type_str)
            else:
                b = _type_bytes(op.type_str)
                for operand in _OPERAND.findall(op.rest):
                    t = comp.symbols.get(operand)
                    if t:
                        b += _type_bytes(t)
            stats.bytes_accessed += b
    cache[key] = stats
    return stats


def analyze_hlo(text: str) -> HloStats:
    comps, entry = _parse_computations(text)
    if entry is None:
        # Fall back: largest computation.
        entry = max(comps, key=lambda n: len(comps[n].ops)) if comps else ""
    cache: Dict[Tuple[str, bool], HloStats] = {}
    return _analyze_comp(entry, comps, cache)
