"""Post-SPMD HLO analysis for the roofline: per-device dot FLOPs, HBM
traffic, and collective payloads — with while-loop trip-count propagation.

``compiled.cost_analysis()`` visits loop bodies ONCE (verified empirically),
so scan-over-layers models would be undercounted by ~num_layers x. This
parser walks the HLO text, finds each computation's execution multiplier
(entry=1; while body/cond x trip count, nested loops multiply), and sums:

- flops: dot instructions (2 * prod(out_shape) * contracted size)
- hbm bytes: per instruction, operands + outputs (fusions are atomic)
- collective bytes: all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute payloads with ring factors
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _parse_shapes(type_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All dtype[shape] tokens in a type string (handles tuples)."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x) if dims else ()
        out.append((dt, shape))
    return out


def _nbytes(type_str: str) -> int:
    tot = 0
    for dt, shape in _parse_shapes(type_str):
        tot += _DTYPE_BYTES[dt] * int(math.prod(shape)) if shape else _DTYPE_BYTES[dt]
    return tot


@dataclass
class Instr:
    name: str
    opcode: str
    result_type: str
    text: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    shapes: Dict[str, str] = field(default_factory=dict)   # symbol -> type str
    root: Optional[str] = None


_OPCODE_RE = re.compile(
    r"^((?:\(|[a-z0-9]+\[)[^=]*?)\s+"          # result type
    r"([a-z0-9\-]+)\(", )


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for line in text.splitlines():
        line = _COMMENT_RE.sub("", line)   # strip /*index=N*/ tuple comments
        stripped = line.strip()
        # computation header: "%name (args) -> type {" or "ENTRY %name ..."
        if stripped.endswith("{") and ("(" in stripped) and "=" not in stripped.split("(")[0]:
            header = stripped
            is_entry = header.startswith("ENTRY")
            m = re.search(r"%?([\w\.\-]+)\s*\(", header.replace("ENTRY", "").strip())
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                if is_entry:
                    entry = cur.name
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        is_root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
        if is_root:
            cur.root = name
        om = _OPCODE_RE.match(rhs)
        if not om:
            # e.g. parameters: "f32[2,3]{1,0} parameter(0)"
            pm = re.match(r"^(.*?)\s+parameter\(", rhs)
            if pm:
                cur.shapes[name] = pm.group(1)
                cur.instrs.append(Instr(name, "parameter", pm.group(1), rhs))
            continue
        rtype, opcode = om.group(1), om.group(2)
        cur.shapes[name] = rtype
        cur.instrs.append(Instr(name, opcode, rtype, rhs))
    return comps, entry


def _trip_count(cond: Computation, while_text: str = "") -> int:
    """Trip count: prefer XLA's known_trip_count backend_config on the while
    instruction; fall back to the largest s32 constant in the condition."""
    m = re.search(r'known_trip_count[^0-9]*(\d+)', while_text)
    if m:
        return int(m.group(1))
    best = 1
    for ins in cond.instrs:
        if ins.opcode == "constant":
            m = re.search(r"constant\((\d+)\)", ins.text)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _multipliers(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    """Execution count per computation, propagating through while loops and
    calls/conditionals. Fusions and reduce-appliers are NOT descended."""
    mult: Dict[str, float] = {entry: 1.0}
    stack = [entry]
    while stack:
        cname = stack.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for ins in comp.instrs:
            if ins.opcode == "while":
                bm = re.search(r"body=%?([\w\.\-]+)", ins.text)
                cm = re.search(r"condition=%?([\w\.\-]+)", ins.text)
                if not bm:
                    continue
                trips = 1
                if cm and cm.group(1) in comps:
                    trips = _trip_count(comps[cm.group(1)], ins.text)
                child = bm.group(1)
                newm = m * trips
                if mult.get(child, 0) < newm:
                    mult[child] = newm
                    stack.append(child)
            elif ins.opcode in ("call", "conditional"):
                for cm2 in re.finditer(r"(?:to_apply|branch_computations)=\{?%?([\w\.\-,% ]+)",
                                       ins.text):
                    for child in re.split(r"[,\s%]+", cm2.group(1)):
                        child = child.strip("}{% ")
                        if child in comps and mult.get(child, 0) < m:
                            mult[child] = m
                            stack.append(child)
    return mult


def _dot_flops(ins: Instr, comp: Computation) -> float:
    """2 * prod(result) * prod(contracted lhs dims)."""
    out_elems = 1
    for dt, shape in _parse_shapes(ins.result_type):
        out_elems = math.prod(shape) if shape else 1
        break
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.text)
    ops = re.findall(r"%([\w\.\-]+)", ins.text)
    if not m or not ops:
        return 2.0 * out_elems
    lhs_type = comp.shapes.get(ops[0], "")
    shapes = _parse_shapes(lhs_type)
    if not shapes:
        return 2.0 * out_elems
    lhs_shape = shapes[0][1]
    k = 1
    for d in (int(x) for x in m.group(1).split(",") if x):
        if d < len(lhs_shape):
            k *= lhs_shape[d]
    return 2.0 * out_elems * k


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   "while", "call", "conditional"}

# ---- fusion-aware traffic model -------------------------------------------
# The raw per-instruction count reflects the UNFUSED CPU HLO; on TPU, XLA
# fuses elementwise/shape chains so intermediates never touch HBM (verified
# ~10-70x overcount on dense training napkin math). The fused model clusters
# fusible ops and counts one read of cluster inputs + one write of cluster
# outputs — the classic XLA fusion traffic model.

# pure pass-throughs: no traffic of their own, values flow through
_ALIAS_OPS = {"tuple", "get-tuple-element", "bitcast", "after-all"}

# elementwise / shape ops that XLA-TPU fuses into loop fusions
_FUSIBLE_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "remainder", "atan2", "and", "or", "xor", "not", "negate", "abs", "sign",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "logistic", "sqrt", "rsqrt", "cbrt", "sine", "cosine", "tan", "erf",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "is-finite",
    "compare", "select", "clamp", "convert", "bitcast-convert",
    "reduce-precision", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "popcnt", "clz",
    "broadcast", "iota", "reshape", "transpose", "slice", "pad",
    "concatenate", "reverse", "copy", "map", "real", "imag", "complex",
    "rng", "rng-bit-generator", "stochastic-convert",
    # XLA-TPU input-fuses reductions with their producers (softmax's exp
    # never hits HBM between the max/sum and the scale); model reduce as a
    # cluster member whose output is the (small) reduced value
    "reduce",
}

# fusible sources that read (almost) nothing
_FREE_SOURCES = {"constant", "iota", "rng", "rng-bit-generator",
                 "partition-id", "replica-id"}


class _UF:
    def __init__(self):
        self.p: Dict[str, str] = {}

    def find(self, x: str) -> str:
        while self.p.setdefault(x, x) != x:
            self.p[x] = self.p[self.p[x]]
            x = self.p[x]
        return x

    def union(self, a: str, b: str):
        self.p[self.find(a)] = self.find(b)


_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _operands(rhs: str) -> List[str]:
    """Operand names inside the op's top-level parens (excludes attribute
    references like to_apply=%add after the closing paren)."""
    i = rhs.find("(")
    if i < 0:
        return []
    depth = 0
    for j in range(i, len(rhs)):
        if rhs[j] == "(":
            depth += 1
        elif rhs[j] == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND_RE.findall(rhs[i:j])
    return _OPERAND_RE.findall(rhs[i:])


_SLICE_OPS = {"dynamic-slice", "slice", "gather"}


def _fusion_body(ins: Instr, comps: Dict[str, "Computation"]):
    """(body, body_opnds, param_name by index, consumers) of a fusion, or
    None when the called computation is unavailable."""
    m = re.search(r"calls=%?([\w\.\-]+)", ins.text)
    if not m or m.group(1) not in comps:
        return None
    body = comps[m.group(1)]
    body_opnds = {i.name: _operands(i.text) for i in body.instrs}
    param_name: Dict[int, str] = {}
    for bi in body.instrs:
        if bi.opcode == "parameter":
            pm = re.search(r"parameter\((\d+)\)", bi.text)
            if pm:
                param_name[int(pm.group(1))] = bi.name
    consumers: Dict[str, List[Instr]] = {}
    for bi in body.instrs:
        for o in body_opnds[bi.name]:
            consumers.setdefault(o, []).append(bi)
    return body, body_opnds, param_name, consumers


def _fusion_param_touch(ins: Instr, comps: Dict[str, "Computation"],
                        operand_idxs: List[int],
                        full: float) -> float:
    """Bytes a fusion actually reads of the operand at ``operand_idxs``:
    a body parameter consumed ONLY by slice/dynamic-slice/gather ops touches
    the sliced rows (stacked scan tensors read one layer per iteration); a
    parameter consumed only as a dynamic-update-slice TARGET is written
    in-place and never read in full."""
    fb = _fusion_body(ins, comps)
    if fb is None:
        return full
    body, body_opnds, param_name, consumers = fb
    touch = 0.0
    for idx in operand_idxs:
        pname = param_name.get(idx)
        if pname is None:
            return full
        cons = consumers.get(pname, [])
        if not cons:
            continue
        if all(c.opcode in _SLICE_OPS for c in cons):
            touch += sum(_nbytes(c.result_type) for c in cons)
        elif all(c.opcode == "dynamic-update-slice"
                 and body_opnds[c.name]
                 and body_opnds[c.name][0] == pname for c in cons):
            pass                          # in-place DUS target
        else:
            return full
    return min(full, touch)


def _fusion_write(ins: Instr, comps: Dict[str, "Computation"]) -> float:
    """Bytes a fusion writes: a root dynamic-update-slice (possibly behind a
    tuple) writes only its update slice (the output aliases the target)."""
    fb = _fusion_body(ins, comps)
    if fb is None:
        return float(_nbytes(ins.result_type))
    body, body_opnds, _, _ = fb
    body_instrs = {i.name: i for i in body.instrs}

    def wb(name: str) -> float:
        bi = body_instrs.get(name)
        if bi is None:
            return 0.0
        if bi.opcode == "dynamic-update-slice":
            ops = body_opnds[bi.name]
            return float(_nbytes(body.shapes.get(ops[1], ""))) \
                if len(ops) > 1 else float(_nbytes(bi.result_type))
        if bi.opcode in _ALIAS_OPS:
            ops = body_opnds[bi.name]
            if bi.opcode == "tuple":
                return sum(wb(o) for o in ops)
            return wb(ops[0]) if ops else 0.0
        return float(_nbytes(bi.result_type))

    return wb(body.root) if body.root else float(_nbytes(ins.result_type))


def _fused_bytes(comp: "Computation", root: Optional[str],
                 comps: Optional[Dict[str, "Computation"]] = None) -> float:
    """HBM traffic of one execution of ``comp`` under the TPU fusion model.

    CPU-XLA emits many SMALL kLoop fusions where TPU-XLA builds large ones,
    so plain per-instruction counting overstates traffic by 10-70x (checked
    against napkin math for dense training). Model: fusible elementwise/
    shape ops AND existing fusion instructions merge into clusters; a
    cluster reads its external inputs once (slice-aware: stacked scan
    tensors touched one layer per iteration) and writes escaping values
    once (DUS-aware: in-place saves write only the slice). dots, reduces,
    collectives, gathers and loop boundaries stay materialization points.
    """
    comps = comps or {}
    instrs = {i.name: i for i in comp.instrs}
    opnds = {i.name: _operands(i.text) for i in comp.instrs}

    def is_member(i: Instr) -> bool:
        return i.opcode in _FUSIBLE_OPS or i.opcode == "fusion"

    # chase aliases to the effective producer value
    def resolve(name: str) -> str:
        seen = 0
        while name in instrs and instrs[name].opcode in _ALIAS_OPS \
                and seen < 64:
            ops = opnds[name]
            if not ops:
                break
            name = ops[0]
            seen += 1
        return name

    def read_size(o_direct: str, o_res: str) -> float:
        """Bytes of the DIRECT operand (an alias like get-tuple-element
        reads its component, never the whole carry tuple behind it)."""
        return float(_nbytes(comp.shapes.get(o_direct,
                                             comp.shapes.get(o_res, ""))))

    uf = _UF()
    for ins in comp.instrs:
        if not is_member(ins):
            continue
        for o in opnds[ins.name]:
            o = resolve(o)
            prod = instrs.get(o)
            if prod is not None and is_member(prod):
                uf.union(ins.name, o)

    consumers: Dict[str, List[str]] = {}
    for ins in comp.instrs:
        for o in opnds[ins.name]:
            consumers.setdefault(resolve(o), []).append(ins.name)

    clusters: Dict[str, List[Instr]] = {}
    for ins in comp.instrs:
        if is_member(ins):
            clusters.setdefault(uf.find(ins.name), []).append(ins)

    def member_touch(mem: Instr, o_res: str, o_direct: str,
                     full: float) -> float:
        if mem.opcode in _SLICE_OPS:
            return min(full, float(_nbytes(mem.result_type)))
        if mem.opcode == "fusion":
            idxs = [i for i, o in enumerate(opnds[mem.name])
                    if resolve(o) == o_res]
            return _fusion_param_touch(mem, comps, idxs, full)
        return full

    total = 0.0
    for cid, members in clusters.items():
        mset = {m.name for m in members}
        # inputs: one read per external value, slice-aware, capped at full
        ext: Dict[str, float] = {}
        full_of: Dict[str, float] = {}
        for mem in members:
            if mem.opcode in _FREE_SOURCES:
                continue
            for o_direct in opnds[mem.name]:
                o = resolve(o_direct)
                prod = instrs.get(o)
                if prod is not None and prod.name in mset:
                    continue              # internal edge: VMEM/VREG only
                if prod is not None and prod.opcode in _FREE_SOURCES:
                    continue
                full = read_size(o_direct, o)
                full_of[o] = full
                ext[o] = ext.get(o, 0.0) + member_touch(mem, o, o_direct,
                                                        full)
        total += sum(min(v, full_of[o]) for o, v in ext.items())
        # outputs: escaping member values materialize once
        for mem in members:
            esc = mem.name == root
            if not esc:
                for c in consumers.get(mem.name, ()):
                    ci = instrs[c]
                    if ci.opcode in _ALIAS_OPS or c not in mset:
                        esc = True        # consumed outside (or via carry)
                        break
            if esc:
                total += _fusion_write(mem, comps) if mem.opcode == "fusion" \
                    else float(_nbytes(mem.result_type))

    for ins in comp.instrs:
        if is_member(ins) or ins.opcode in _ALIAS_OPS \
                or ins.opcode == "parameter" \
                or ins.opcode in _FREE_SOURCES:
            continue
        if ins.opcode in ("while", "call", "conditional"):
            continue                      # cost carried by the child body
        base = ins.opcode.replace("-start", "")
        if base in _COLLECTIVES or ins.opcode.endswith("-done"):
            continue                      # accounted separately
        # materializing op: one read per unique operand + one write
        if ins.opcode in ("gather", "dynamic-slice"):
            total += 2.0 * _nbytes(ins.result_type)
            continue
        if ins.opcode in ("dynamic-update-slice", "scatter"):
            ops = opnds[ins.name]
            upd = _nbytes(comp.shapes.get(resolve(ops[1]), "")) \
                if len(ops) > 1 else 0
            total += 2.0 * upd
            continue
        seen_mat: set = set()
        for o_direct in opnds[ins.name]:
            o = resolve(o_direct)
            if o in seen_mat:
                continue
            seen_mat.add(o)
            prod = instrs.get(o)
            if prod is not None and prod.opcode in _FREE_SOURCES:
                continue
            total += read_size(o_direct, o)
        total += _nbytes(ins.result_type)
    return total


@dataclass
class HloSummary:
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0           # fusion-aware model (primary)
    hbm_bytes_raw: float = 0.0       # per-instruction count (unfused HLO)
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    collective_counts: Dict[str, int] = field(default_factory=dict)
    num_whiles: int = 0
    trip_counts: List[int] = field(default_factory=list)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def _group_size(text: str, default: int = 1) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", text)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", text)
    if m:
        return len(m.group(1).split(","))
    return default


def analyze(text: str) -> HloSummary:
    comps, entry = parse_hlo(text)
    if entry is None:
        # fall back: biggest computation
        entry = max(comps, key=lambda c: len(comps[c].instrs)) if comps else ""
    mult = _multipliers(comps, entry)
    s = HloSummary()
    for cname, m in mult.items():
        comp = comps.get(cname)
        if comp is None:
            continue
        s.hbm_bytes += _fused_bytes(comp, comp.root, comps) * m
        for ins in comp.instrs:
            base = ins.opcode.replace("-start", "")
            if base in _COLLECTIVES:
                payload = _nbytes(ins.result_type)
                n = _group_size(ins.text)
                if base == "all-reduce":
                    eff = 2.0 * payload * (n - 1) / max(n, 1)
                elif base in ("all-gather", "reduce-scatter", "all-to-all"):
                    eff = payload * (n - 1) / max(n, 1)
                else:
                    eff = float(payload)
                s.collective_bytes[base] = s.collective_bytes.get(base, 0.0) + eff * m
                s.collective_counts[base] = s.collective_counts.get(base, 0) + int(m)
                s.hbm_bytes += payload * m
                s.hbm_bytes_raw += payload * m
                continue
            if ins.opcode == "while":
                s.num_whiles += 1
                cm = re.search(r"condition=%?([\w\.\-]+)", ins.text)
                if cm and cm.group(1) in comps:
                    s.trip_counts.append(_trip_count(comps[cm.group(1)],
                                                     ins.text))
                continue
            if ins.opcode in _SKIP_BYTES_OPS or ins.opcode.endswith("-done"):
                continue
            if ins.opcode == "dot":
                s.dot_flops += _dot_flops(ins, comp) * m
            # HBM traffic: operands + result (fusion treated as atomic), with
            # sparse-access ops counted by touched bytes, not operand size:
            #  - gather/dynamic-slice read only the selected rows
            #  - dynamic-update-slice/scatter write in place (donated buffers)
            if ins.opcode in ("gather", "dynamic-slice"):
                s.hbm_bytes_raw += 2.0 * _nbytes(ins.result_type) * m
                continue
            if ins.opcode in ("dynamic-update-slice", "scatter"):
                ops = re.findall(r"%([\w\.\-]+)", ins.text)
                upd = _nbytes(comp.shapes.get(ops[1], "")) if len(ops) > 1 else 0
                s.hbm_bytes_raw += 2.0 * upd * m
                continue
            ops = re.findall(r"%([\w\.\-]+)", ins.text)
            obytes = sum(_nbytes(comp.shapes.get(o, "")) for o in set(ops))
            s.hbm_bytes_raw += (obytes + _nbytes(ins.result_type)) * m
    return s


# --------------------------------------------------------------------------
# Roofline terms — peak rates come from a BackendSpec (default: TPU v5e,
# the part the dry-run compiles for)
# --------------------------------------------------------------------------
from repro.core.backend import TPU_V5E, BackendSpec  # noqa: E402

# Aliases of the v5e spec's peak rates, per chip.
PEAK_FLOPS_BF16 = TPU_V5E.peak_flops_bf16   # per chip
HBM_BW = TPU_V5E.hbm_bw                     # per chip
ICI_BW = TPU_V5E.ici_bw                     # per link


def roofline_terms(summary: HloSummary, *,
                   flops_override: Optional[float] = None,
                   spec: BackendSpec = TPU_V5E) -> Dict[str, float]:
    """All terms are seconds (per-device program => per-chip time)."""
    flops = flops_override if flops_override is not None else summary.dot_flops
    return {
        "compute_s": flops / spec.peak_flops_bf16,
        "memory_s": summary.hbm_bytes / spec.hbm_bw,
        "collective_s": summary.total_collective_bytes / spec.ici_bw,
    }
