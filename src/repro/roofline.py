"""Three-term roofline analysis from compiled HLO (dry-run artifacts).

``compiled.cost_analysis()`` counts each ``while`` body ONCE (verified
empirically — a scan of 10 matmuls reports the FLOPs of one), so a model
scanned over layers would under-report by ~n_layers.  This module therefore
walks the *scheduled HLO text* itself:

  * builds a per-computation symbol table (%var -> shape),
  * multiplies every op's cost by the product of enclosing loop trip counts
    (XLA annotates ``backend_config={"known_trip_count":{"n":...}}`` on
    ``while`` ops lowered from lax.scan/fori_loop),
  * FLOPs: ``dot`` ops as 2 * prod(output) * prod(contracting dims)
    (+ convolutions, negligible here),
  * HBM bytes: for each top-level fusion/op, operands + outputs (a fusion's
    parameters/results are exactly its HBM traffic on TPU),
  * collective bytes: operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (+ their async -start
    forms, counted once).

Terms (TPU v5e): compute = FLOPs / peak, memory = bytes / HBM_bw,
collective = bytes / ICI_bw — all per device, seconds.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2,
    "f32": 4, "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUE_FALSE_RE = re.compile(
    r"(?:true_computation|false_computation)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Op:
    name: str
    result_type: str
    opcode: str
    rest: str           # full RHS text (operands + attrs)
    is_root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    symtab: Dict[str, str]          # %var -> result type string


def parse_computations(hlo: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    entry = ""
    for line in hlo.splitlines():
        if line.startswith(("HloModule", "//")) or not line.strip():
            continue
        if not line.startswith(" ") and "->" in line and "{" in line:
            m = _COMP_RE.match(line.strip())
            if m:
                current = Computation(m.group(1), [], {})
                comps[current.name] = current
                if line.strip().startswith("ENTRY"):
                    entry = current.name
            continue
        if line.strip() == "}":
            continue
        if current is None:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        is_root = line.lstrip().startswith("ROOT ")
        var, rhs = m.group(1), m.group(2)
        # rhs = "TYPE opcode(...)..." ; type may be a tuple "(a, b)".
        rhs = rhs.strip()
        if rhs.startswith("("):
            depth = 0
            for i, ch in enumerate(rhs):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            type_str, rest = rhs[:i + 1], rhs[i + 1:].strip()
        else:
            sp = rhs.index(" ")
            type_str, rest = rhs[:sp], rhs[sp + 1:].strip()
        opm = re.match(r"([\w\-]+)", rest)
        opcode = opm.group(1) if opm else ""
        current.symtab[var] = type_str
        current.ops.append(Op(var, type_str, opcode, rest, is_root))
    return comps, entry


def _multipliers(comps: Dict[str, Computation], entry: str
                 ) -> Dict[str, float]:
    """Computation -> product of enclosing trip counts (entry = 1)."""
    mult: Dict[str, float] = {entry: 1.0}
    stack = [entry]
    while stack:
        cname = stack.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for op in comp.ops:
            trip = 1.0
            if op.opcode == "while":
                tm = _TRIP_RE.search(op.rest)
                trip = float(tm.group(1)) if tm else 1.0
            callees: List[Tuple[str, float]] = []
            for rex, w in ((_BODY_RE, trip), (_COND_RE, trip + 1),
                           (_CALL_RE, 1.0)):
                for name in rex.findall(op.rest):
                    callees.append((name, w))
            bm = _BRANCH_RE.search(op.rest)
            if bm:
                branches = _OPERAND_RE.findall(bm.group(1))
                for bname in branches:
                    callees.append((bname, 1.0 / max(len(branches), 1)))
            for name in _TRUE_FALSE_RE.findall(op.rest):
                callees.append((name, 0.5))
            for name, w in callees:
                nm = m * w
                if mult.get(name, 0.0) < nm:
                    mult[name] = nm
                    stack.append(name)
    return mult


_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "while", "conditional", "call", "after-all", "add-dependency",
    "copy-start", "copy-done", "partition-id", "replica-id", "iota",
}

#: Ops a TPU compiler fuses into their producers/consumers — counting their
#: operands as HBM traffic would model a machine with no fusion at all
#: (the CPU backend's HLO is barely fused, so the raw per-op sum grossly
#: overestimates TPU HBM bytes).  Elementwise/shape ops are therefore
#: skipped; dots, reductions, scatters/gathers, data movement and
#: while-carried tensors remain counted (write + read ≈ 2x each tensor,
#: which is the correct steady-state traffic model).
_FUSED_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "rsqrt", "sqrt", "power", "convert", "compare", "select",
    "and", "or", "xor", "not", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "clamp", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "is-finite", "popcnt",
    "broadcast", "reshape", "slice", "rev", "map", "reduce-precision",
    "bitcast-convert", "stochastic-convert", "cosine", "sine", "erf",
    "logistic", "cbrt", "atan2", "remainder", "expm1", "log1p", "copy",
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


@dataclasses.dataclass
class RooflineCounts:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    per_collective: Dict[str, float] = dataclasses.field(default_factory=dict)
    dots: int = 0

    def terms(self, peak_flops: float, hbm_bw: float, ici_bw: float
              ) -> Dict[str, float]:
        return {
            "compute_s": self.flops / peak_flops,
            "memory_s": self.hbm_bytes / hbm_bw,
            "collective_s": self.collective_bytes / ici_bw,
        }


def _operand_bytes(op: Op, symtab: Dict[str, str]) -> int:
    # Operands live inside the first (...) group of rest.
    lp = op.rest.find("(")
    if lp < 0:
        return 0
    depth, rp = 0, len(op.rest)
    for i in range(lp, len(op.rest)):
        depth += op.rest[i] == "("
        depth -= op.rest[i] == ")"
        if depth == 0:
            rp = i
            break
    total = 0
    for name in _OPERAND_RE.findall(op.rest[lp:rp + 1]):
        t = symtab.get(name)
        if t:
            total += _shape_bytes(t)
    return total


def _dot_flops(op: Op, symtab: Dict[str, str]) -> float:
    out_elems = 1
    for d in _shape_dims(op.result_type):
        out_elems *= d
    cm = _CONTRACT_RE.search(op.rest)
    contracting = 1
    if cm:
        lp = op.rest.find("(")
        operands = _OPERAND_RE.findall(op.rest[lp:]) if lp >= 0 else []
        lhs_t = symtab.get(operands[0]) if operands else None
        dims = _shape_dims(lhs_t) if lhs_t else []
        for idx in (cm.group(1).split(",") if cm.group(1) else []):
            i = int(idx)
            if i < len(dims):
                contracting *= dims[i]
    return 2.0 * out_elems * contracting


def _operand_types(op: Op, symtab: Dict[str, str]) -> List[str]:
    lp = op.rest.find("(")
    if lp < 0:
        return []
    depth, rp = 0, len(op.rest)
    for i in range(lp, len(op.rest)):
        depth += op.rest[i] == "("
        depth -= op.rest[i] == ")"
        if depth == 0:
            rp = i
            break
    return [symtab[n] for n in _OPERAND_RE.findall(op.rest[lp:rp + 1])
            if n in symtab]


def _root_of(comp: Computation) -> Optional[Op]:
    for op in comp.ops:
        if op.is_root:
            return op
    return comp.ops[-1] if comp.ops else None


def _op_hbm_bytes(op: Op, comp: Computation,
                  comps: Dict[str, Computation]) -> float:
    """HBM traffic model for one op.

    In-place accumulator updates (dynamic-update-slice — lax.scan stacking
    its ys, gradient accumulation) touch only the UPDATE slice, not the
    carried buffer: counting the buffer per iteration would charge a scan
    O(n^2) traffic.  The same applies to fusions whose root is a DUS (the
    usual compiled form): the buffer-sized parameter is aliased, so it is
    subtracted and the update counted instead."""
    out_b = _shape_bytes(op.result_type)
    if op.opcode == "dynamic-update-slice":
        ops_t = _operand_types(op, comp.symtab)
        upd = _shape_bytes(ops_t[1]) if len(ops_t) > 1 else 0
        return 2.0 * upd                       # write + later read
    if op.opcode == "dynamic-slice":
        return 2.0 * out_b
    if op.opcode == "fusion":
        cm = _CALL_RE.search(op.rest)
        callee = comps.get(cm.group(1)) if cm else None
        if callee is None:
            return _operand_bytes(op, comp.symtab) + out_b
        operand_b = _fusion_param_bytes(callee)
        root = _root_of(callee)
        if root is not None and root.opcode == "dynamic-update-slice":
            rt = _operand_types(root, callee.symtab)
            upd = _shape_bytes(rt[1]) if len(rt) > 1 else 0
            # the buffer param is aliased in-place: subtract it
            buf = max((_shape_bytes(o.result_type) for o in callee.ops
                       if o.opcode == "parameter"
                       and _shape_bytes(o.result_type) == out_b), default=0)
            return max(operand_b - buf, 0) + 2.0 * upd
        return operand_b + out_b
    return _operand_bytes(op, comp.symtab) + out_b


def _fusion_param_bytes(callee: Computation) -> float:
    """Bytes a fusion actually READS: a parameter consumed only through
    dynamic-slice ops is charged the slice sizes, not the full buffer —
    the compiled form of lax.scan streaming blocks out of a stacked xs
    (charging the stack per iteration would be O(n^2))."""
    params = {o.name: _shape_bytes(o.result_type) for o in callee.ops
              if o.opcode == "parameter"}
    sliced: Dict[str, float] = {}
    other_use = set()
    for o in callee.ops:
        if o.opcode == "parameter":
            continue
        lp = o.rest.find("(")
        if lp < 0:
            continue
        depth, rp = 0, len(o.rest)
        for i in range(lp, len(o.rest)):
            depth += o.rest[i] == "("
            depth -= o.rest[i] == ")"
            if depth == 0:
                rp = i
                break
        names = _OPERAND_RE.findall(o.rest[lp:rp + 1])
        for i, nm in enumerate(names):
            if nm not in params:
                continue
            if o.opcode in ("dynamic-slice", "slice") and i == 0:
                sliced[nm] = sliced.get(nm, 0.0) \
                    + _shape_bytes(o.result_type)
            else:
                other_use.add(nm)
    total = 0.0
    for nm, full in params.items():
        if nm in sliced and nm not in other_use:
            total += min(sliced[nm], full)
        else:
            total += full
    return total


def analyze_hlo(hlo: str) -> RooflineCounts:
    comps, entry = parse_computations(hlo)
    mult = _multipliers(comps, entry)
    # Ops inside fusion callees count FLOPs (a dot fused with its epilogue
    # is still a dot) but not HBM bytes (intermediate values stay on-chip).
    fusion_callees = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "fusion":
                cm = _CALL_RE.search(op.rest)
                if cm:
                    fusion_callees.add(cm.group(1))
    counts = RooflineCounts()
    for cname, comp in comps.items():
        m = mult.get(cname)
        if m is None:
            continue                        # unreachable (dead) computation
        in_fusion = cname in fusion_callees
        for op in comp.ops:
            if op.opcode == "dot":
                counts.flops += m * _dot_flops(op, comp.symtab)
                counts.dots += 1
            is_coll = any(op.opcode.startswith(c) for c in _COLLECTIVES)
            if is_coll and not op.opcode.endswith("-done"):
                b = _operand_bytes(op, comp.symtab)
                counts.collective_bytes += m * b
                kind = op.opcode.replace("-start", "")
                counts.per_collective[kind] = (
                    counts.per_collective.get(kind, 0.0) + m * b)
            if (in_fusion or op.opcode in _SKIP_BYTES_OPS or is_coll
                    or op.opcode in _FUSED_ELEMENTWISE):
                continue
            counts.hbm_bytes += m * _op_hbm_bytes(op, comp, comps)
    return counts
