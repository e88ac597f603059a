"""Scan-aware HLO cost analysis for the roofline report.

``compiled.cost_analysis()`` visits each ``while`` body **once**, so for a
scan-over-layers model it under-counts FLOPs/bytes by ~num_layers x (verified
empirically — see EXPERIMENTS.md §Roofline methodology). This module parses
``compiled.as_text()`` (the post-SPMD, per-device HLO), builds the call graph
(entry -> while bodies -> fusions), multiplies every computation's cost by its
execution count (``backend_config={"known_trip_count":...}``), and reports:

* ``flops``           — dot FLOPs (2 * prod(out) * prod(contracting)) plus
                        elementwise/reduce FLOPs, per device;
* ``hbm_bytes``       — operand+result bytes of every *scheduled* op
                        (fusion-internal ops excluded: they live in
                        VMEM/registers on TPU), per device;
* ``collective_bytes``— sum of operand sizes of all-gather / all-reduce /
                        reduce-scatter / all-to-all / collective-permute
                        (spec definition), plus a per-device *traffic*
                        estimate using ring factors, per device;
* per-collective breakdown for the §Perf iteration log.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = {
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "all-gather-start", "all-reduce-start",
    "collective-permute-start",
}

# ops whose output elements each cost ~1 flop
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "tanh", "log",
    "log-plus-one", "rsqrt", "sqrt", "power", "cosine", "sine", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "logistic", "cbrt",
    "atan2", "erf", "remainder", "select", "clamp",
}

_NO_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}

# ops that on TPU would fuse into neighbours (no HBM round-trip of their
# own); excluded from the *fused* bytes estimate. The conservative
# ``hbm_bytes`` keeps them (CPU-fusion boundaries = upper bound).
_FUSABLE = _ELEMENTWISE | {
    "broadcast", "compare", "convert", "reshape", "slice", "and", "or",
    "not", "xor", "sign", "is-finite", "reduce-precision", "map",
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\s*\{")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes_elems(shape_txt: str) -> Tuple[int, int]:
    """Total (bytes, elements) of a shape string (tuple-aware)."""
    total_b, total_e = 0, 0
    for dt, dims in _SHAPE_RE.findall(shape_txt):
        if dt not in _DTYPE_BYTES and dt not in ("token",):
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total_e += n
        total_b += n * _DTYPE_BYTES.get(dt, 4)
    return total_b, total_e


def _shape_dims(shape_txt: str) -> List[int]:
    m = _SHAPE_RE.search(shape_txt)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: List[str]
    attrs: str
    is_root: bool = False
    raw_operands: str = ""


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    by_name: Dict[str, Instr] = field(default_factory=dict)


def _parse_instr(line: str) -> Optional[Instr]:
    is_root = line.lstrip().startswith("ROOT ")
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rhs = m.group(1), m.group(2)
    rhs = rhs.strip()
    # shape: tuple or single
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    shape, rest = rhs[: i + 1], rhs[i + 1 :].strip()
                    break
        else:
            return None
    else:
        sm = re.match(r"([a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?(?:\s*)?)", rhs)
        if not sm:
            return None
        shape, rest = sm.group(1), rhs[sm.end() :].strip()
    om = re.match(r"([\w\-]+)\(", rest)
    if not om:
        return None
    opcode = om.group(1)
    # operand section: names only, no nested parens
    end = rest.find(")", om.end())
    if end < 0:
        return None
    operand_txt = rest[om.end() : end]
    operands = re.findall(r"%([\w.\-]+)", operand_txt)
    attrs = rest[end + 1 :]
    return Instr(name=name, shape=shape, opcode=opcode, operands=operands,
                 attrs=attrs, is_root=is_root, raw_operands=operand_txt)


def parse_hlo(txt: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for line in txt.splitlines():
        if cur is None:
            m = _COMP_HDR_RE.match(line.strip())
            if m and "(" in line:
                cur = Computation(name=m.group(2))
                if m.group(1):
                    entry = m.group(2)
            continue
        s = line.strip()
        if s == "}":
            comps[cur.name] = cur
            cur = None
            continue
        ins = _parse_instr(s)
        if ins:
            cur.instrs.append(ins)
            cur.by_name[ins.name] = ins
    return comps, entry


@dataclass
class CostReport:
    flops: float = 0.0
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0                 # upper bound (CPU-fusion boundaries)
    hbm_bytes_fused: float = 0.0           # TPU estimate (elementwise fused away)
    collective_bytes: float = 0.0          # spec: sum of operand sizes
    collective_traffic_bytes: float = 0.0  # ring-factor per-device estimate
    collectives: Dict[str, float] = field(default_factory=dict)   # opcode -> operand bytes
    collective_counts: Dict[str, int] = field(default_factory=dict)
    collective_details: List[Tuple[str, str, float, int]] = field(default_factory=list)
    bytes_by_opcode: Dict[str, float] = field(default_factory=dict)
    while_trips: List[int] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "dot_flops": self.dot_flops,
            "hbm_bytes": self.hbm_bytes,
            "hbm_bytes_fused": self.hbm_bytes_fused,
            "collective_bytes": self.collective_bytes,
            "collective_traffic_bytes": self.collective_traffic_bytes,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "bytes_by_opcode": self.bytes_by_opcode,
            "while_trips": self.while_trips,
        }


def _group_size(attrs: str, num_partitions: int) -> int:
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(attrs)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return num_partitions


def _dot_flops(ins: Instr, comp: Computation) -> float:
    out_b, out_e = _shape_bytes_elems(ins.shape)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
    contract = 1
    if m and ins.operands:
        lhs = comp.by_name.get(ins.operands[0])
        if lhs is not None:
            dims = _shape_dims(lhs.shape)
            for idx in (int(i) for i in m.group(1).split(",") if i):
                if idx < len(dims):
                    contract *= dims[idx]
    return 2.0 * out_e * contract


def _shape_of(comp: Computation, name: str) -> str:
    ins = comp.by_name.get(name)
    return ins.shape if ins is not None else ""


def _fusion_traffic(comps: Dict[str, Computation], comp: Computation,
                    ins: Instr) -> float:
    """HBM traffic of one fusion op, seeing through dynamic-(update-)slice:

    * an operand consumed ONLY by dynamic-slice ops costs the slice bytes,
      not the full buffer (a scan body reads one layer of the stacked
      params/cache per iteration);
    * an operand consumed ONLY as the in-place target of dynamic-update-
      slice costs the update bytes (one token written into a 32k cache);
    * a root that is a dynamic-update-slice (or a tuple of them) writes the
      update bytes, not the whole aliased buffer.
    """
    body = None
    for m in re.finditer(r"calls=%?([\w.\-]+)", ins.attrs):
        body = comps.get(m.group(1))
    if body is None:
        ob, _ = _shape_bytes_elems(ins.shape)
        opnd = sum(_shape_bytes_elems(_shape_of(comp, o))[0] for o in ins.operands)
        return ob + opnd, ob + opnd

    # map parameter index -> body instruction
    params: Dict[int, Instr] = {}
    for bi in body.instrs:
        if bi.opcode == "parameter":
            pm = re.match(r"\s*(\d+)", bi.raw_operands)
            idx = int(pm.group(1)) if pm else len(params)
            params[idx] = bi
    # fall back: parameters in order of appearance
    if not params:
        order = [bi for bi in body.instrs if bi.opcode == "parameter"]
        params = dict(enumerate(order))

    _CAST_OPS = {"convert", "bitcast", "copy", "reshape", "broadcast",
                 "transpose"}

    def _trace(name: str) -> Optional[Instr]:
        """Follow unary cast/layout ops back to the producing op."""
        seen = 0
        e = body.by_name.get(name)
        while e is not None and e.opcode in _CAST_OPS and e.operands and seen < 8:
            e = body.by_name.get(e.operands[0])
            seen += 1
        return e

    def dus_update_bytes(dus: Instr) -> float:
        if len(dus.operands) >= 2:
            return _shape_bytes_elems(_shape_of(body, dus.operands[1]))[0] or 0.0
        return 0.0

    total = 0.0
    root = next((bi for bi in body.instrs if bi.is_root), None)
    root_real = _trace(root.name) if root is not None else None
    if root_real is not None and root_real.opcode == "dynamic-update-slice":
        total += 2 * dus_update_bytes(root_real)
    elif root_real is not None and root_real.opcode == "scatter":
        upd = (_shape_bytes_elems(_shape_of(body, root_real.operands[2]))[0]
               if len(root_real.operands) > 2 else 0.0)
        total += 3 * upd
    elif root is not None and root.opcode == "tuple":
        for o in root.operands:
            e = _trace(o)
            if e is not None and e.opcode == "dynamic-update-slice":
                total += 2 * dus_update_bytes(e)
            else:
                total += _shape_bytes_elems(_shape_of(body, o))[0]
    else:
        total += _shape_bytes_elems(ins.shape)[0]

    # --- operand side
    for idx, oname in enumerate(ins.operands):
        pin = params.get(idx)
        full = _shape_bytes_elems(_shape_of(comp, oname))[0]
        if pin is None:
            total += full
            continue
        consumers = [bi for bi in body.instrs if pin.name in bi.operands]
        if consumers and all(c.opcode == "dynamic-slice" for c in consumers):
            total += sum(_shape_bytes_elems(c.shape)[0] for c in consumers)
        elif consumers and all(
            c.opcode in ("dynamic-update-slice", "scatter") and c.operands
            and c.operands[0] == pin.name for c in consumers
        ):
            total += 0.0  # in-place update target: write counted on out side
        else:
            total += full

    # TPU-estimate side: a fusion whose every non-parameter op is a pure
    # cast/layout op would not exist in a native-bf16 TPU program (the CPU
    # backend upcasts bf16 dots to f32, round-tripping whole caches)
    pure_cast = all(
        bi.opcode in _CAST_OPS or bi.opcode in ("parameter", "constant", "tuple")
        for bi in body.instrs
    )
    fused_total = 0.0 if pure_cast else total
    return total, fused_total


def analyze_hlo_text(txt: str, num_partitions: Optional[int] = None) -> CostReport:
    if num_partitions is None:
        m = re.search(r"num_partitions=(\d+)", txt)
        num_partitions = int(m.group(1)) if m else 1
    comps, entry = parse_hlo(txt)
    if entry is None:
        # fall back: biggest computation
        entry = max(comps, key=lambda k: len(comps[k].instrs)) if comps else None
    rep = CostReport()
    if entry is None:
        return rep

    def attr_comp(attrs: str, key: str) -> List[str]:
        out = []
        for m in re.finditer(key + r"=%?([\w.\-]+)", attrs):
            out.append(m.group(1))
        return out

    def walk(comp_name: str, mult: float, count_bytes: bool):
        comp = comps.get(comp_name)
        if comp is None:
            return
        for ins in comp.instrs:
            op = ins.opcode
            out_b, out_e = _shape_bytes_elems(ins.shape)
            opnd_b = 0
            for o in ins.operands:
                src = comp.by_name.get(o)
                if src is not None:
                    b, _ = _shape_bytes_elems(src.shape)
                    opnd_b += b
            # ---- flops
            if op == "dot":
                f = _dot_flops(ins, comp) * mult
                rep.flops += f
                rep.dot_flops += f
            elif op in _ELEMENTWISE:
                rep.flops += out_e * mult
            elif op in ("reduce", "reduce-window"):
                _, in_e = (0, 0)
                if ins.operands:
                    src = comp.by_name.get(ins.operands[0])
                    if src is not None:
                        _, in_e = _shape_bytes_elems(src.shape)
                rep.flops += in_e * mult
            # ---- bytes
            if count_bytes and op not in _NO_BYTES and op != "while":
                traffic_fused = None
                if op == "fusion":
                    traffic, traffic_fused = _fusion_traffic(comps, comp, ins)
                elif op == "dynamic-slice":
                    traffic = 2.0 * out_b  # read slice + write slice
                elif op == "dynamic-update-slice":
                    upd = (_shape_bytes_elems(_shape_of(comp, ins.operands[1]))[0]
                           if len(ins.operands) > 1 else out_b)
                    traffic = 2.0 * upd  # in-place read-modify-write of slice
                elif op == "scatter":
                    upd = (_shape_bytes_elems(_shape_of(comp, ins.operands[2]))[0]
                           if len(ins.operands) > 2 else out_b)
                    traffic = 3.0 * upd  # read idx+update, RMW the slots
                else:
                    traffic = out_b + opnd_b
                rep.hbm_bytes += traffic * mult
                rep.bytes_by_opcode[op] = rep.bytes_by_opcode.get(op, 0.0) + \
                    traffic * mult
                if op not in _FUSABLE:
                    rep.hbm_bytes_fused += (
                        traffic_fused if traffic_fused is not None else traffic
                    ) * mult
            # ---- collectives
            if op in _COLLECTIVES:
                base = op.replace("-start", "")
                gs = _group_size(ins.attrs, num_partitions)
                rep.collective_bytes += opnd_b * mult
                rep.collectives[base] = rep.collectives.get(base, 0.0) + opnd_b * mult
                rep.collective_counts[base] = rep.collective_counts.get(base, 0) + int(mult)
                if base == "all-gather":
                    traffic = out_b * (gs - 1) / gs
                elif base == "all-reduce":
                    traffic = 2.0 * opnd_b * (gs - 1) / gs
                elif base == "reduce-scatter":
                    traffic = opnd_b * (gs - 1) / gs
                elif base == "all-to-all":
                    traffic = opnd_b * (gs - 1) / gs
                else:  # collective-permute
                    traffic = opnd_b
                rep.collective_traffic_bytes += traffic * mult
                rep.collective_details.append((base, ins.shape, opnd_b * mult, gs))
            # ---- recursion
            if op == "while":
                trips = 1
                tm = _TRIP_RE.search(ins.attrs)
                if tm:
                    trips = int(tm.group(1))
                rep.while_trips.append(trips)
                for b in attr_comp(ins.attrs, "body"):
                    walk(b, mult * trips, True)
                for c in attr_comp(ins.attrs, "condition"):
                    walk(c, mult * trips, False)
            elif op == "fusion":
                for c in attr_comp(ins.attrs, "calls"):
                    walk(c, mult, False)  # fusion-internal = VMEM, no HBM bytes
            elif op == "call":
                for c in attr_comp(ins.attrs, "to_apply"):
                    walk(c, mult, count_bytes)
            elif op == "conditional":
                for c in attr_comp(ins.attrs, "branch_computations"):
                    walk(c, mult, count_bytes)

    walk(entry, 1.0, True)
    return rep


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())


def analyze_compiled(compiled) -> dict:
    """Full report for a compiled executable: parsed costs + memory stats."""
    txt = compiled.as_text()
    rep = analyze_hlo_text(txt)
    out = rep.as_dict()
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    try:
        ca = xla_cost_analysis(compiled)
        out["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        out["xla_cost_analysis"] = {"error": str(e)}
    return out
