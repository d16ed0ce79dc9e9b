"""Post-SPMD HLO text analyzer: FLOPs, HBM-traffic model, collective bytes.

Why parse text?  ``compiled.cost_analysis()`` counts every ``while`` body
ONCE (verified empirically: an 8-step scan of matmuls reports 1/8 of the
unrolled FLOPs), and it has no collective accounting at all.  The compiled
module text has everything needed:

- instruction result shapes -> a symbol table of operand sizes,
- ``dot`` ops with contracting dims -> exact matmul FLOPs,
- ``while`` ops with ``condition=%c, body=%b`` and the loop bound as the
  ``s32[] constant(N)`` in the condition -> trip-count multipliers,
- collective ops with ``replica_groups`` -> per-chip link-time ring model.

All numbers are PER DEVICE (the SPMD module is the per-device program);
multiply by chip count for global figures.

HBM-traffic model: post-fusion, each top-level instruction reads its
operands from HBM and writes its result (fusion internals never touch HBM),
so traffic = sum over non-trivial instructions of (operand + result bytes)
x trip multiplier.  Pure-layout ops (parameter/tuple/gte/bitcast/constant)
are excluded.  This is the standard fusion-boundary traffic estimate; it is
exact for weights and caches and slightly pessimistic for reused operands.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Optional

__all__ = ["analyze_hlo", "HloStats", "WhileLoop", "while_loops"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2|c64|c128)\[([0-9,]*)\]")
# instruction/computation lines appear in two prints: the optimized
# module text (``%name = f32[] op(...)``, headers ``%comp (args) -> ty {``)
# and the unoptimized pre-SPMD text (no ``%``, headers ``comp.N {``)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_COMP_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->[^{]*)?\{")
_WHILE_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations|"
                       r"true_computation|false_computation)="
                       r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_GROUPS_NEW_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
# x64 loop counters print as s64 — both widths bound trip counts
_CONST_RE = re.compile(r"s(?:32|64)\[\]\s+constant\((\d+)\)")

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_TRIVIAL = {
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant",
    "after-all", "iota", "partition-id", "replica-id", "copy-start",
    "copy-done",
}


def _type_dims(type_str: str):
    """-> (bytes, dims_of_first_array, dtype).  Tuples sum bytes."""
    total = 0
    first_dims = None
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims_s = m.group(1), m.group(2)
        dims = [int(d) for d in dims_s.split(",") if d] if dims_s else []
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dtype]
        if first_dims is None:
            first_dims = dims
    return total, (first_dims or []), None


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    result_bytes: int
    result_dims: list
    operands: list
    attrs: str


_NAME_RE = re.compile(r"^[\w.\-]+$")


def _split_operands(rest: str) -> tuple[list[str], str]:
    """rest starts right after the opening '('; returns (operand names, attrs).

    Scheduled modules print operands WITH their type, e.g.
    ``dot(f32[4,16]{1,0} %lhs, f32[16,128]{1,0} %rhs)``, and tuple-typed
    operands contain commas inside the type.  Keep only the trailing
    ``%name`` token of each comma piece and drop type fragments.
    """
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                inner, attrs = rest[:i], rest[i + 1:]
                ops = []
                for piece in inner.split(","):
                    toks = piece.split()
                    if not toks:
                        continue
                    tok = toks[-1].lstrip("%")
                    if _NAME_RE.match(tok) and not tok[0].isdigit():
                        ops.append(tok)
                return ops, attrs
    return [], rest


def _parse(text: str):
    comps: dict[str, list[Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and "{" in line:
            cur = mc.group(1)
            comps[cur] = []
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, type_str, opcode = mi.groups()
        rest = line[mi.end():]
        operands, attrs = _split_operands(rest)
        rbytes, rdims, _ = _type_dims(type_str)
        comps[cur].append(Instr(name, opcode, rbytes, rdims, operands, attrs))
    return comps


@dataclasses.dataclass
class HloStats:
    """Per-device totals (trip-count corrected)."""
    flops: float
    hbm_traffic_bytes: float
    collective_bytes: dict            # opcode -> operand bytes
    collective_link_seconds: float    # ring-model per-chip link time
    while_trips: dict                 # body comp -> trip count
    notes: list
    #: body computations of while ops whose condition holds NO integer
    #: constant — their trips fell back to ``default_trip`` and the
    #: loop has no static bound (dltlint DL001 errors on these)
    unbounded_whiles: list = dataclasses.field(default_factory=list)


def analyze_hlo(text: str, link_bw: float = 50e9,
                default_trip: int = 1) -> HloStats:
    comps = _parse(text)
    notes: list[str] = []
    if not comps:
        return HloStats(flops=0.0, hbm_traffic_bytes=0.0,
                        collective_bytes={}, collective_link_seconds=0.0,
                        while_trips={},
                        notes=["no computations parsed from HLO text"])

    # symbol tables: per-comp name -> (bytes, dims); global fallback
    sym: dict[str, dict[str, tuple]] = {}
    gsym: dict[str, tuple] = {}
    for cname, instrs in comps.items():
        tab = {}
        for ins in instrs:
            tab[ins.name] = (ins.result_bytes, ins.result_dims)
            gsym[ins.name] = (ins.result_bytes, ins.result_dims)
        sym[cname] = tab

    def look(cname, op):
        return sym.get(cname, {}).get(op) or gsym.get(op) or (0, [])

    # ---- trip counts: collect s32[] constants per computation ----------------
    cur = None
    comp_consts: dict[str, list[int]] = defaultdict(list)
    for line in text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and "{" in line:
            cur = mc.group(1)
            continue
        if cur:
            for m in _CONST_RE.finditer(line):
                comp_consts[cur].append(int(m.group(1)))

    # ---- computation multipliers (BFS over call graph) -----------------------
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            entry = m.group(1) if m else None
            break
    if entry is None:
        entry = next(iter(comps))

    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    while_trips: dict[str, int] = {}
    unbounded: list[str] = []
    order = [entry]
    seen = {entry}
    idx = 0
    while idx < len(order):
        cname = order[idx]
        idx += 1
        m = mult[cname]
        for ins in comps.get(cname, []):
            wm = _WHILE_RE.search(ins.attrs)
            if ins.opcode == "while" and wm:
                cond, body = wm.groups()
                cond_consts = comp_consts.get(cond, [])
                if not cond_consts and body not in unbounded:
                    unbounded.append(body)
                trips = max(cond_consts or [default_trip])
                trips = max(trips, 1)
                while_trips[body] = trips
                for sub in (cond, body):
                    mult[sub] += m * trips
                    if sub not in seen:
                        seen.add(sub)
                        order.append(sub)
            else:
                subs = []
                for cm in _CALLS_RE.finditer(ins.attrs):
                    for sub in re.split(r",\s*", cm.group(1)):
                        sub = sub.lstrip("%")
                        if sub in comps:
                            subs.append(sub)
                # data-dependent branches execute ONE branch per visit:
                # weight by expected execution (uniform over branches).
                # For the chunked-attention causal block skip this matches
                # the exact causal count (half the off-diagonal blocks).
                w = m / max(len(subs), 1) if ins.opcode == "conditional" else m
                for sub in subs:
                    mult[sub] += w
                    if sub not in seen:
                        seen.add(sub)
                        order.append(sub)

    # fusions: internals don't touch HBM; but dots can't live in fusions on
    # this backend path — verified by construction in tests.
    fusion_bodies = set()
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.opcode == "fusion":
                cm = _CALLS_RE.search(ins.attrs)
                if cm:
                    # the greedy capture can run into ", metadata" — keep
                    # only tokens that name real computations.
                    for sub in re.split(r",\s*", cm.group(1)):
                        sub = sub.lstrip("%")
                        if sub in comps:
                            fusion_bodies.add(sub)

    # ---- aggregate ------------------------------------------------------------
    flops = 0.0
    traffic = 0.0
    coll_bytes: dict[str, float] = defaultdict(float)
    coll_secs = 0.0

    for cname, instrs in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = cname in fusion_bodies
        for ins in instrs:
            opc = ins.opcode

            if opc == "dot":
                lhs = look(cname, ins.operands[0]) if ins.operands else (0, [])
                cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
                cdims = [int(d) for d in cm.group(1).split(",") if d] if cm else []
                contract = 1
                for d in cdims:
                    if d < len(lhs[1]):
                        contract *= lhs[1][d]
                out_elems = 1
                for d in ins.result_dims:
                    out_elems *= d
                flops += 2.0 * out_elems * contract * m

            if in_fusion:
                continue  # fusion internals: no HBM traffic, no collectives

            base = opc.replace("-start", "")
            if base in COLLECTIVES:
                ob = sum(look(cname, o)[0] for o in ins.operands)
                coll_bytes[base] += ob * m
                g = None
                gm = _GROUPS_NEW_RE.search(ins.attrs)
                if gm:
                    g = int(gm.group(2))
                else:
                    gm2 = _GROUPS_OLD_RE.search(ins.attrs)
                    if gm2:
                        g = gm2.group(1).count(",") + 1
                g = g or 2
                if base == "all-reduce":
                    secs = 2.0 * (g - 1) / g * ob / link_bw
                elif base == "all-gather":
                    secs = (g - 1) * ob / link_bw
                elif base in ("reduce-scatter", "all-to-all",
                              "ragged-all-to-all"):
                    secs = (g - 1) / g * ob / link_bw
                else:  # collective-permute
                    secs = ob / link_bw
                coll_secs += secs * m

            if opc.endswith("-done") or opc in _TRIVIAL:
                continue
            ob = sum(look(cname, o)[0] for o in ins.operands)
            traffic += (ob + ins.result_bytes) * m

    return HloStats(
        flops=flops,
        hbm_traffic_bytes=traffic,
        collective_bytes=dict(coll_bytes),
        collective_link_seconds=coll_secs,
        while_trips=while_trips,
        notes=notes,
        unbounded_whiles=unbounded,
    )


@dataclasses.dataclass(frozen=True)
class WhileLoop:
    """One ``while`` op of a module."""
    body: str       # its body computation
    op_name: str    # the op's ``metadata`` op_name ("" where it has none)
    nested: int     # ``while`` ops reachable from the body, at any depth


def while_loops(text: str) -> list:
    """Every ``while`` op of an HLO module, with the loops inside it.

    A loop counts as inside another where its op sits in the body or in
    any computation the body calls (fusions, calls, branches, nested
    loops).  The TPU compiler expands an f64 dot, Cholesky or triangular
    solve into such nested loops, so ``nested`` says whether a loop body
    is straight-line code.
    """
    comps = _parse(text)

    def callees(cname):
        subs = set()
        for ins in comps.get(cname, []):
            for cm in _CALLS_RE.finditer(ins.attrs):
                subs.update(s.lstrip("%") for s in re.split(r",\s*", cm.group(1)))
        return subs & comps.keys()

    memo: dict[str, int] = {}

    def inside(cname):
        if cname not in memo:
            memo[cname] = 0                 # a cycle adds nothing
            memo[cname] = (sum(i.opcode == "while" for i in comps[cname])
                           + sum(inside(s) for s in callees(cname)))
        return memo[cname]

    loops = []
    for instrs in comps.values():
        for ins in instrs:
            wm = _WHILE_RE.search(ins.attrs)
            if ins.opcode == "while" and wm:
                op = re.search(r'op_name="([^"]*)"', ins.attrs)
                body = wm.group(2)
                loops.append(WhileLoop(body, op.group(1) if op else "",
                                       inside(body) if body in comps else 0))
    return loops
