"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically: a scan of L matmuls reports ~1 matmul of FLOPs regardless of
L).  Since this framework deliberately scans over layers/microbatches to
keep compile times sane, all roofline terms would be wrong by ~L x micro.

This module re-derives the terms from ``compiled.as_text()``:

  * computations are parsed into instruction lists;
  * ``while`` trip counts come from the max s32 constant in the condition
    computation (lax.scan lowers to 0..N step-1 loops);
  * FLOPs: 2 * output_elems * contraction_size for every dot, recursing
    through fusions/whiles (x trip) and calls;
  * bytes: operand + output bytes per instruction at fusion granularity
    (XLA's own bytes-accessed convention), x trips inside loops;
  * collective bytes: output sizes of all-gather/all-reduce/reduce-scatter/
    all-to-all/collective-permute (+ async starts), x trips — FSDP
    all-gathers living inside the layer scan are the dominant term and are
    exactly what the once-counted version misses;
  * ``conditional`` branches are averaged (noted: zamba2's every-6-layers
    attention is overcounted by ~2.7x under this rule; the roofline stays
    conservative).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# instructions that move no real data
_BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element",
                "bitcast", "after-all", "iota", "partition-id", "replica-id"}


def _type_bytes(typestr: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(typestr):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _type_elems(typestr: str) -> int:
    m = _SHAPE_RE.search(typestr)
    if not m:
        return 0
    n = 1
    if m.group(2):
        for d in m.group(2).split(","):
            n *= int(d)
    return n


@dataclass
class Instruction:
    name: str
    typestr: str
    op: str
    line: str
    operands: List[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instructions: List[Instruction] = field(default_factory=list)


_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
# the result type is a tuple or one space-free token; TPU layouts carry
# parentheses inside it (``{1,0:T(8,128)S(1)}``)
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(.*?\)|\S+?)\s+"
    r"([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w\.\-]+)")


def parse_module(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HEADER.match(line.strip())
            if m and line.strip().endswith("{"):
                cur = Computation(name=m.group(2))
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        mi = _INSTR.match(line)
        if mi:
            name, typestr, op = mi.group(1), mi.group(2), mi.group(3)
            paren = line[mi.end() - 1:]
            # operands: %refs inside the first balanced paren group
            depth = 0
            end = 0
            for i, ch in enumerate(paren):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            ops = _OPERAND.findall(paren[:end + 1])
            cur.instructions.append(Instruction(
                name=name, typestr=typestr, op=op, line=line, operands=ops))
    return comps, entry


def _attr_comp(line: str, key: str) -> Optional[str]:
    m = re.search(rf"{key}=%?([\w\.\-]+)", line)
    return m.group(1) if m else None


def _branch_comps(line: str) -> List[str]:
    m = re.search(r"branch_computations=\{([^}]*)\}", line)
    if not m:
        return []
    return [x.strip().lstrip("%") for x in m.group(1).split(",")]


def _dot_flops(ins: Instruction, sizes: Dict[str, str]) -> float:
    out_elems = _type_elems(ins.typestr)
    lhs_t = sizes.get(ins.operands[0], "") if ins.operands else ""
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
    k = 1
    if m and lhs_t:
        dims_m = _SHAPE_RE.search(lhs_t)
        if dims_m and dims_m.group(2):
            dims = [int(d) for d in dims_m.group(2).split(",")]
            for idx in (int(x) for x in m.group(1).split(",") if x):
                if idx < len(dims):
                    k *= dims[idx]
    return 2.0 * out_elems * k


@dataclass
class Costs:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_kind: Dict[str, float] = field(default_factory=dict)

    def add(self, other: "Costs", scale: float = 1.0) -> None:
        self.flops += other.flops * scale
        self.bytes += other.bytes * scale
        self.coll_bytes += other.coll_bytes * scale
        for k, v in other.coll_by_kind.items():
            self.coll_by_kind[k] = self.coll_by_kind.get(k, 0.0) + v * scale


class HloAnalyzer:
    def __init__(self, text: str):
        self.comps, self.entry = parse_module(text)
        # global result-type map (names are module-unique in practice)
        self.sizes: Dict[str, str] = {}
        for c in self.comps.values():
            for ins in c.instructions:
                self.sizes[ins.name] = ins.typestr
        self._memo: Dict[str, Costs] = {}

    def trip_count(self, cond_name: str) -> int:
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1
        best = 1
        for ins in comp.instructions:
            if ins.op == "constant":
                m = re.match(r"s32\[\]", ins.typestr)
                c = re.search(r"constant\((\d+)\)", ins.line)
                if m and c:
                    best = max(best, int(c.group(1)))
        return best

    def _dus_bytes(self, callee: Optional[str]) -> Optional[float]:
        """If a fusion updates a big buffer in place (dynamic-update-slice —
        scan stacking, KV-cache writes), charge the slice-sized work only:
        XLA aliases donated buffers, so the full-buffer passes (and the CPU
        backend's full-buffer f32<->bf16 converts) never touch HBM on TPU.
        Returns None when the fusion has no dus."""
        comp = self.comps.get(callee) if callee else None
        if comp is None:
            return None
        dus = [ci for ci in comp.instructions
               if ci.op == "dynamic-update-slice"]
        if not dus:
            return None
        target_b = max(_type_bytes(ci.typestr) for ci in dus)
        total = 0.0
        for ci in comp.instructions:
            if ci.op in _BOOKKEEPING:
                continue
            out_b = _type_bytes(ci.typestr)
            if out_b >= 0.5 * target_b:
                continue                    # buffer-sized op: aliased/in-place
            total += 2 * out_b
        return total

    _MOVEMENT_OPS = {"dynamic-slice", "slice", "convert", "copy",
                     "reshape", "transpose"}

    def _movement_bytes(self, callee: Optional[str]) -> Optional[float]:
        """Pure data-movement fusions (slice/convert/transpose chains):
        charge 2 x the narrowest tensor in the chain.  The CPU backend
        promotes bf16 params to f32 and re-materializes both widths; a TPU
        bf16 lowering moves the narrow version once."""
        comp = self.comps.get(callee) if callee else None
        if comp is None:
            return None
        sizes = []
        for ci in comp.instructions:
            if ci.op in _BOOKKEEPING:
                continue
            if ci.op not in self._MOVEMENT_OPS:
                return None
            sizes.append(_type_bytes(ci.typestr))
        if not sizes:
            return None
        return 2.0 * min(sizes)

    def _is_convert_only(self, callee: str) -> bool:
        comp = self.comps.get(callee)
        if comp is None:
            return False
        compute = [ci for ci in comp.instructions
                   if ci.op not in _BOOKKEEPING]
        return bool(compute) and all(ci.op in ("convert", "copy")
                                     for ci in compute)

    def _fusion_input_bytes(self, ins: Instruction,
                            callee: Optional[str]) -> float:
        """Bytes actually READ from each fusion operand.

        A scan body receives the full stacked (L, ...) parameter but only
        dynamic-slices one layer out — charging the full operand would
        overcount HBM traffic by ~L x trips.  If every consumer of a fusion
        parameter is a dynamic-slice, charge the slice outputs instead.
        """
        comp = self.comps.get(callee) if callee else None
        if comp is None:
            return float(sum(_type_bytes(self.sizes.get(o, ""))
                             for o in ins.operands))
        # parameter index -> instruction name, and name -> consumers
        param_names: Dict[int, str] = {}
        for ci in comp.instructions:
            if ci.op == "parameter":
                m = re.search(r"parameter\((\d+)\)", ci.line)
                if m:
                    param_names[int(m.group(1))] = ci.name
        total = 0.0
        for i, operand in enumerate(ins.operands):
            full = _type_bytes(self.sizes.get(operand, ""))
            pname = param_names.get(i)
            if pname is None:
                total += full
                continue
            consumers = [ci for ci in comp.instructions
                         if pname in ci.operands]
            if consumers and all(ci.op == "dynamic-slice"
                                 for ci in consumers):
                total += sum(_type_bytes(ci.typestr) for ci in consumers)
            else:
                total += full
        return total

    def costs(self, comp_name: Optional[str] = None) -> Costs:
        comp_name = comp_name or self.entry
        if comp_name in self._memo:
            return self._memo[comp_name]
        total = Costs()
        comp = self.comps.get(comp_name)
        if comp is None:
            return total
        self._memo[comp_name] = total      # break cycles defensively
        for ins in comp.instructions:
            op = ins.op
            base = op[:-6] if op.endswith("-start") else op
            if op.endswith("-done") or op in _BOOKKEEPING:
                continue
            # data movement at this level (fusion-granular)
            out_b = _type_bytes(ins.typestr)
            in_b = sum(_type_bytes(self.sizes.get(o, ""))
                       for o in ins.operands)
            if op == "while":
                body = _attr_comp(ins.line, "body")
                cond = _attr_comp(ins.line, "condition")
                trips = self.trip_count(cond) if cond else 1
                if body:
                    total.add(self.costs(body), trips)
                if cond:
                    total.add(self.costs(cond), trips)
                continue
            if op == "conditional":
                branches = _branch_comps(ins.line)
                if branches:
                    sub = Costs()
                    for b in branches:
                        sub.add(self.costs(b), 1.0 / len(branches))
                    total.add(sub)
                continue
            if op == "dynamic-update-slice":
                # in-place update (XLA aliases donated buffers): traffic is
                # the updated slice, not the whole target buffer
                upd = _type_bytes(self.sizes.get(ins.operands[1], "")) \
                    if len(ins.operands) > 1 else out_b
                total.bytes += 2 * upd
                continue
            if op in ("fusion", "call", "custom-call", "map"):
                callee = _attr_comp(ins.line, "calls") \
                    or _attr_comp(ins.line, "to_apply")
                if callee and self._is_convert_only(callee):
                    # CPU-backend f32 promotion artifact: TPU bf16 lowering
                    # has no materialized convert — don't charge traffic.
                    continue
                dus_b = self._dus_bytes(callee)
                if dus_b is not None:
                    total.bytes += dus_b
                    continue
                mv_b = self._movement_bytes(callee)
                if mv_b is not None:
                    total.bytes += mv_b
                    continue
                total.bytes += out_b + self._fusion_input_bytes(ins, callee)
                if callee:
                    inner = self.costs(callee)
                    total.flops += inner.flops
                    total.coll_bytes += inner.coll_bytes
                    for k, v in inner.coll_by_kind.items():
                        total.coll_by_kind[k] = \
                            total.coll_by_kind.get(k, 0.0) + v
                continue
            if base in _COLLECTIVES:
                total.coll_bytes += out_b
                total.coll_by_kind[base] = \
                    total.coll_by_kind.get(base, 0.0) + out_b
                total.bytes += out_b + in_b
                continue
            if op in ("dot", "convolution"):
                total.flops += _dot_flops(ins, self.sizes)
            total.bytes += out_b + in_b
        self._memo[comp_name] = total
        return total


def analyze_hlo_text(text: str) -> Costs:
    return HloAnalyzer(text).costs()


def top_contributors(text: str, metric: str = "bytes",
                     k: int = 20) -> List[Tuple[float, str, str]]:
    """Profile: (weighted_cost, computation, instruction-line) heavy hitters.

    Walks the module like ``costs`` but attributes per-instruction costs
    multiplied by the enclosing loops' trip counts — the dry-run's
    stand-in for a wall-clock profile (per §Perf methodology).
    """
    az = HloAnalyzer(text)
    out: List[Tuple[float, str, str]] = []

    def walk(comp_name: str, scale: float, seen: tuple):
        comp = az.comps.get(comp_name)
        if comp is None or comp_name in seen:
            return
        seen = seen + (comp_name,)
        for ins in comp.instructions:
            op = ins.op
            if op.endswith("-done") or op in _BOOKKEEPING:
                continue
            if op == "while":
                body = _attr_comp(ins.line, "body")
                cond = _attr_comp(ins.line, "condition")
                trips = az.trip_count(cond) if cond else 1
                if body:
                    walk(body, scale * trips, seen)
                continue
            if op == "conditional":
                for b in _branch_comps(ins.line):
                    walk(b, scale * 0.5, seen)
                continue
            callee = _attr_comp(ins.line, "calls") \
                or _attr_comp(ins.line, "to_apply")
            if op in ("fusion", "call", "map") and callee:
                if az._is_convert_only(callee):
                    continue
                dus_b = az._dus_bytes(callee)
                if metric == "bytes":
                    if dus_b is not None:
                        cost = dus_b
                    else:
                        cost = _type_bytes(ins.typestr) \
                            + az._fusion_input_bytes(ins, callee)
                else:
                    cost = az.costs(callee).flops
                if cost:
                    out.append((cost * scale, comp_name, ins.line[:160]))
                continue
            if metric == "bytes":
                cost = _type_bytes(ins.typestr) + sum(
                    _type_bytes(az.sizes.get(o, "")) for o in ins.operands)
            else:
                cost = _dot_flops(ins, az.sizes) \
                    if op in ("dot", "convolution") else 0.0
            if cost:
                out.append((cost * scale, comp_name, ins.line[:160]))

    walk(az.entry, 1.0, ())
    out.sort(key=lambda t: -t[0])
    return out[:k]
