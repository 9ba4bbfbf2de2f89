"""Collective-byte accounting and data-movement checks from compiled HLO
text.

``cost_analysis()`` has no collective-byte entry, so we parse the compiled
module text. The compiled module is the per-device (SPMD-partitioned)
program, so every shape we read is a *per-device* shape; the returned byte
counts are bytes-on-the-wire per device, using standard ring-algorithm
factors:

    all-reduce        2 * B * (n-1)/n
    all-gather        B_out * (n-1)/n
    reduce-scatter    B_in * (n-1)/n
    all-to-all        B * (n-1)/n
    collective-permute B

(n = collective group size parsed from ``replica_groups``).

``loop_data_movement`` lists the copies, concatenations, slices, pads and
dynamic-update-slices a compiled program runs inside its loops, alone or
as fusions of nothing else: ops that do no arithmetic, only move data,
once per iteration.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# instruction definition: %name = <shape> opcode(...)  /  %name = (tuple) op(...)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_GROUPS_ITOA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of one shape token list (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    operand_bytes: int
    group_size: int
    line: str

    @property
    def wire_bytes(self) -> float:
        n = max(self.group_size, 1)
        f = (n - 1) / n if n > 1 else 0.0
        if self.kind == "all-reduce":
            return 2.0 * self.operand_bytes * f
        if self.kind == "all-gather":
            return self.result_bytes * f
        if self.kind == "reduce-scatter":
            return self.operand_bytes * f
        if self.kind == "all-to-all":
            return self.operand_bytes * f
        return float(self.operand_bytes)  # collective-permute


@dataclass
class CollectiveStats:
    ops: List[CollectiveOp] = field(default_factory=list)

    @property
    def total_wire_bytes(self) -> float:
        return sum(o.wire_bytes for o in self.ops)

    def by_kind(self) -> Dict[str, Tuple[int, float]]:
        out: Dict[str, Tuple[int, float]] = {}
        agg: Dict[str, List[CollectiveOp]] = defaultdict(list)
        for o in self.ops:
            agg[o.kind].append(o)
        for k, v in agg.items():
            out[k] = (len(v), sum(o.wire_bytes for o in v))
        return out

    def summary(self) -> dict:
        return {
            "total_wire_bytes": self.total_wire_bytes,
            "num_ops": len(self.ops),
            "by_kind": {k: {"count": c, "wire_bytes": b}
                        for k, (c, b) in self.by_kind().items()},
        }


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_ITOA_RE.search(line)
    if m:
        return int(m.group(2))          # [num_groups, group_size]<=[...]
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, num_devices: int = 1) -> CollectiveStats:
    """Parse the per-device HLO module for collective ops.

    Async pairs (``all-gather-start``/``-done``) are counted once on the
    start op. ``num_devices`` is the fallback group size when
    ``replica_groups`` is empty (= all devices).
    """
    shapes: Dict[str, str] = {}
    defs: List[Tuple[str, str, str, str]] = []   # (name, shape, op, line)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, shape_str, op = m.group(1), m.group(2), m.group(3)
        shapes[name] = shape_str
        defs.append((name, shape_str, op, line))

    stats = CollectiveStats()
    for name, shape_str, op, line in defs:
        base = op[:-6] if op.endswith("-start") else op
        if base not in COLLECTIVE_OPS or op.endswith("-done"):
            continue
        # operands: %names inside the call parens (skip metadata/regions)
        try:
            arg_str = line.split(op + "(", 1)[1]
        except IndexError:
            continue
        arg_str = arg_str.split(")", 1)[0]
        operand_bytes = 0
        for om in _OPERAND_RE.finditer(arg_str):
            operand_bytes += _shape_bytes(shapes.get(om.group(1), ""))
        result_bytes = _shape_bytes(shape_str)
        if op.endswith("-start") and base == "all-gather":
            # start result is a tuple (operand, result); take the larger half
            result_bytes = max(result_bytes - operand_bytes, operand_bytes)
        stats.ops.append(CollectiveOp(
            kind=base, result_bytes=result_bytes,
            operand_bytes=operand_bytes,
            group_size=_group_size(line, num_devices), line=line.strip()))
    return stats


def count_op(hlo_text: str, opname: str) -> int:
    """Count instruction definitions of a given opcode (e.g. 'fusion')."""
    n = 0
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m and m.group(3) == opname:
            n += 1
    return n


# a computation's header line: "%name (params) -> shape {" / "ENTRY %name ..."
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
# computations an instruction runs: loop bodies and conditions, branches,
# called computations (not a fusion's ``calls=``: its ops never reach
# memory on their own)
_CALLS_RE = re.compile(r"\b(body|condition|to_apply|branch_computations|"
                       r"true_computation|false_computation)="
                       r"(\{[^}]*\}|%?[\w.\-]+)")
DATA_MOVEMENT_OPS = ("copy", "concatenate", "slice", "pad",
                     "dynamic-update-slice")
# what a fusion may hold and still only move data: a fusion made of these
# alone (and at least one of DATA_MOVEMENT_OPS or a transpose) is a
# relayout however XLA packaged it. A broadcast is not among them: a
# fusion that broadcasts a row to the fleet writes new values.
_MOVE_ONLY = frozenset(DATA_MOVEMENT_OPS) | {
    "parameter", "constant", "bitcast", "reshape", "transpose",
    "dynamic-slice", "tuple", "get-tuple-element"}
_FUSION_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")

def _computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        head = None if line[:1].isspace() else _COMP_RE.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _called(line: str, keys=None) -> List[str]:
    return [c.strip().lstrip("%") for m in _CALLS_RE.finditer(line)
            if keys is None or m.group(1) in keys
            for c in m.group(2).strip("{}").split(",") if c.strip()]


def _moves_only(lines: List[str]) -> bool:
    ops = {m.group(3) for m in map(_DEF_RE.match, lines) if m}
    return ops <= _MOVE_ONLY and bool(
        ops & (set(DATA_MOVEMENT_OPS) | {"transpose"}))


def loop_data_movement(hlo_text: str) -> List[Tuple[str, str, str, int]]:
    """``(computation, name, opcode, bytes)`` of every data-movement op
    (``DATA_MOVEMENT_OPS``) in a non-fusion computation reachable from a
    ``while`` body, and of every fusion there whose fused computation
    only moves data (``_MOVE_ONLY``; its opcode reads ``fusion``) — what
    a compiled program copies each time round a loop."""
    comps = _computations(hlo_text)
    todo = [c for lines in comps.values() for line in lines
            if " while(" in line for c in _called(line, ("body",))]
    seen, found = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            m = _DEF_RE.match(line)
            if m and m.group(3) in DATA_MOVEMENT_OPS:
                found.append((comp, m.group(1), m.group(3),
                              _shape_bytes(m.group(2))))
            elif m and m.group(3) == "fusion":
                fused = _FUSION_CALLS_RE.search(line)
                if fused and _moves_only(comps.get(fused.group(1), [])):
                    found.append((comp, m.group(1), "fusion",
                                  _shape_bytes(m.group(2))))
            todo += _called(line)
    return found
