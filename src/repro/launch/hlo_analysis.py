"""Roofline terms from a compiled dry-run artifact (deliverable g).

XLA's ``compiled.cost_analysis()`` counts every ``while`` body ONCE (probed:
a jax.lax.scan of 8 matmuls reports 1/8 of the true FLOPs), and the HLO text
prints operands as bare names. So this module analyzes the post-SPMD HLO
*structurally*:

* split the module into computations, build a per-computation symbol table
  (%name -> shape) from result declarations;
* walk the call graph from ENTRY, multiplying by each while op's
  ``backend_config known_trip_count`` (jax scans always have static trips);
* FLOPs   = 2 * prod(result dims) * prod(contracting dims) per dot
  (+ convolutions), loop-multiplied — the MFU convention (elementwise ignored);
* HBM bytes = sum of (result + operand) bytes of top-level ops (fusion
  internals excluded: post-fusion only fusion boundaries touch HBM);
* collective wire bytes per chip, by kind (n = collective group size):
      all-reduce          2 * S * (n-1)/n     (ring RS+AG)
      all-gather          S_full * (n-1)/n
      reduce-scatter      S_shard * (n-1)
      all-to-all          S * (n-1)/n
      collective-permute  S

Hardware model (TPU v5e-class, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI with 3 usable link-pairs on a 2D torus axis pair.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link per chip
ICI_LINKS = 3             # usable links per chip (v5e 2D torus: 4; derate)
DCN_BW = 5e9              # bytes/s per chip across pods

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_COMP_START_RE = re.compile(r"^(ENTRY\s+)?%([\w\.\-]+)\s*\(.*\)\s*->\s*.*\{$")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"?(\d+)')
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_SIMPLE_RESULT_RE = re.compile(
    r"^[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?")
_OPCODE_AFTER_RE = re.compile(r"^\s*([\w\-]+)\(")


def _split_result(rest: str):
    """Split 'rest' (after 'name = ') into (result_text, opcode)."""
    if rest.startswith("("):          # tuple result: match parens by depth
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    res = rest[: i + 1]
                    m = _OPCODE_AFTER_RE.match(rest[i + 1:])
                    return res, (m.group(1) if m else "")
        return rest, ""
    m = _SIMPLE_RESULT_RE.match(rest)
    if not m:
        return "", ""
    res = m.group(0)
    om = _OPCODE_AFTER_RE.match(rest[m.end():])
    return res, (om.group(1) if om else "")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops whose result/operands do NOT touch HBM at top level
_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "while", "iota", "partition-id", "replica-id",
    "rng-get-and-update-state", "get-dimension-size", "call", "conditional",
    "bitcast-convert", "reshape",
}


def _shape_bytes_list(text: str) -> List[int]:
    return [_dtype_prod(d, s) for d, s in _SHAPE_RE.findall(text)]


def _dtype_prod(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _dims_of(text: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dt, ds = m.group(1), m.group(2)
    dims = [int(x) for x in ds.split(",")] if ds else []
    return dt, dims


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    line: str
    result_bytes: int
    result_shape: Optional[Tuple[str, List[int]]]


@dataclasses.dataclass
class Computation:
    name: str
    ops: Dict[str, Op]
    order: List[str]


def parse_module(hlo: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        m = _COMP_START_RE.match(line)
        if m:
            name = m.group(2)
            cur = Computation(name, {}, [])
            comps[name] = cur
            if m.group(1):
                entry = name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        name, rest = dm.group(1), dm.group(2)
        res_text, opcode = _split_result(rest)
        rbytes = sum(_shape_bytes_list(res_text))
        op = Op(name, opcode, line, rbytes, _dims_of(res_text))
        cur.ops[name] = op
        cur.order.append(name)
    return comps, entry


def _group_info(line: str, default: int, pod_size: int) -> Tuple[int, bool]:
    """(group_size, crosses_pod). A collective crosses the DCN iff any
    group contains devices from different pods (device_id // pod_size)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        import numpy as _np
        n_groups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")] if m.group(4)
                else list(range(len(dims))))
        ids = _np.arange(int(_np.prod(dims))).reshape(dims).transpose(perm)
        groups = ids.reshape(n_groups, gsize)
        crosses = bool((_np.ptp(groups // pod_size, axis=1) > 0).any())
        return gsize, crosses
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        members = [int(x) for x in m.group(1).split(",") if x.strip()]
        gsize = max(len(members), 1)
        crosses = len({x // pod_size for x in members}) > 1
        return gsize, crosses
    return default, default > pod_size


def _wire_bytes(kind: str, size: int, n: int) -> float:
    frac = (n - 1) / max(n, 1)
    if kind == "all-reduce":
        return 2.0 * size * frac
    if kind == "all-gather":
        return size * frac                    # size = full gathered result
    if kind == "reduce-scatter":
        return size * (n - 1)                 # size = scattered shard result
    if kind == "all-to-all":
        return size * frac
    return float(size)                        # collective-permute: one hop


def _dot_flops(op: Op, comp: Computation) -> float:
    """2 * prod(result) * prod(lhs contracting dims)."""
    if op.result_shape is None:
        return 0.0
    _, rdims = op.result_shape
    out = 1
    for d in rdims:
        out *= d
    cm = _CONTRACT_RE.search(op.line)
    contract = 1
    if cm:
        idxs = [int(x) for x in cm.group(1).split(",") if x.strip()]
        # first operand inside the call parens is lhs
        call = op.line[op.line.index("(", op.line.index(op.opcode)) + 1:]
        names = _OPERAND_RE.findall(call)
        if names:
            lhs = comp.ops.get(names[0])
            if lhs is not None and lhs.result_shape is not None:
                _, ldims = lhs.result_shape
                for i in idxs:
                    if i < len(ldims):
                        contract *= ldims[i]
    return 2.0 * out * contract


def _conv_flops(op: Op) -> float:
    # rough: 2 * prod(result) * (kernel spatial * in_channels) — parse the
    # rhs shape from the line's window attr is complex; fall back to result
    # size * 2 (convolutions are absent from the LM zoo; audio frontend is a
    # stub). Recorded so nothing silently drops.
    return 2.0 * (op.result_bytes // max(_DTYPE_BYTES.get(
        op.result_shape[0], 4), 1)) if op.result_shape else 0.0


def _called(line: str) -> List[str]:
    out = []
    for m in re.finditer(r"(body|condition|calls|to_apply|branch_computations)="
                         r"(\{[^}]*\}|%[\w\.\-]+)", line):
        blob = m.group(2)
        out.extend(_OPERAND_RE.findall(blob) if blob.startswith("{")
                   else [blob[1:]])
    return out


def operand_names(op: Op) -> List[str]:
    try:
        call = op.line[op.line.index("(", op.line.index(op.opcode)) + 1:]
    except ValueError:
        return []
    depth, end = 1, len(call)
    for i, ch in enumerate(call):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return _OPERAND_RE.findall(call[:end])


def operand_bytes(op: Op, comp: Computation) -> int:
    total = 0
    for nm in operand_names(op):
        o = comp.ops.get(nm)
        if o is not None and o.opcode not in ("constant",):
            total += o.result_bytes
    return total


def nth_operand_bytes(op: Op, comp: Computation, n: int) -> int:
    names = operand_names(op)
    if n < len(names):
        o = comp.ops.get(names[n])
        if o is not None:
            return o.result_bytes
    return op.result_bytes // 8   # fallback: small fraction


def fusion_touch_bytes(op: Op, comp: Computation, comps: Dict[str, Computation]
                       ) -> int:
    """Touch-accurate fusion traffic: a fused dynamic-slice reads only the
    slice, a fused dynamic-update-slice writes only the update — billing the
    full buffers would charge a whole KV cache per chunk (probed)."""
    called = _called(op.line)
    body = comps.get(called[0]) if called else None
    if body is None:
        return op.result_bytes + operand_bytes(op, comp)
    in_bytes = 0
    params_ = [o for o in body.ops.values() if o.opcode == "parameter"]
    consumers: Dict[str, List[Op]] = {p.name: [] for p in params_}
    for o in body.ops.values():
        for nm in operand_names(o):
            if nm in consumers:
                consumers[nm].append(o)
    for p in params_:
        cons = consumers[p.name]
        if cons and all(c.opcode in ("dynamic-slice", "slice", "gather")
                        for c in cons):
            in_bytes += sum(c.result_bytes for c in cons)
        else:
            in_bytes += p.result_bytes
    root = None
    for o in body.ops.values():
        if "ROOT" in o.line:
            root = o
    out_bytes = op.result_bytes
    if root is not None and root.opcode == "dynamic-update-slice":
        names = operand_names(root)
        upd = body.ops.get(names[1]) if len(names) > 1 else None
        out_bytes = upd.result_bytes if upd is not None else out_bytes // 8
        if names and names[0] in body.ops:   # aliased buffer input
            in_bytes = max(in_bytes - body.ops[names[0]].result_bytes, 0)
    return in_bytes + out_bytes


def top_level_bytes(op: Op, comp: Computation,
                    comps: Dict[str, Computation]) -> int:
    """HBM bytes charged to one non-collective, non-control op."""
    oc = op.opcode
    if oc in _FREE_OPS or not oc:
        return 0
    if oc == "fusion":
        return fusion_touch_bytes(op, comp, comps)
    if oc in ("dynamic-slice", "gather", "slice"):
        return 2 * op.result_bytes
    if oc == "dynamic-update-slice":
        return 2 * nth_operand_bytes(op, comp, 1)
    if oc == "scatter":
        return 2 * nth_operand_bytes(op, comp, 2)
    if oc == "copy":
        return op.result_bytes          # aliased/elided on TPU; 1x write
    return op.result_bytes + operand_bytes(op, comp)


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``compiled.cost_analysis()`` -> a flat {metric: value} dict (None,
    for a backend that reports no costs, becomes {})."""
    return dict(cost or {})


@dataclasses.dataclass
class Analysis:
    flops: float                 # per-device, loop-multiplied
    hbm_bytes: float             # per-device, loop-multiplied
    wire_bytes: float            # per-device collective wire bytes
    by_kind: Dict[str, float]
    n_collectives: int
    unknown_trip_loops: int
    ici_bytes: float
    dcn_bytes: float


def analyze(hlo: str, n_devices: int, pod_size: int = 256) -> Analysis:
    comps, entry = parse_module(hlo)
    if entry is None:
        raise ValueError("no ENTRY computation found")

    # computations called as fusion bodies / reduction lambdas: not traversed
    # for bytes, but fusion bodies ARE traversed for dot FLOPs.
    flops = 0.0
    hbm = 0.0
    wire = 0.0
    by_kind: Dict[str, float] = {}
    ici_b = 0.0
    dcn_b = 0.0
    n_coll = 0
    unknown = 0

    def fusion_flops(name: str, mult: float, seen: frozenset) -> float:
        if name not in comps or name in seen:
            return 0.0
        total = 0.0
        comp = comps[name]
        for op_name in comp.order:
            op = comp.ops[op_name]
            if op.opcode == "dot":
                total += mult * _dot_flops(op, comp)
            elif op.opcode == "convolution":
                total += mult * _conv_flops(op)
            elif op.opcode == "fusion":
                for c in _called(op.line):
                    total += fusion_flops(c, mult, seen | {name})
        return total

    def walk(name: str, mult: float, seen: frozenset):
        nonlocal flops, hbm, wire, n_coll, unknown, ici_b, dcn_b
        if name not in comps or name in seen or mult <= 0:
            return
        comp = comps[name]
        for op_name in comp.order:
            op = comp.ops[op_name]
            oc = op.opcode
            if oc == "dot":
                flops += mult * _dot_flops(op, comp)
                hbm += mult * (op.result_bytes + operand_bytes(op, comp))
            elif oc == "convolution":
                flops += mult * _conv_flops(op)
                hbm += mult * (op.result_bytes + operand_bytes(op, comp))
            elif oc == "fusion":
                for c in _called(op.line):
                    flops += fusion_flops(c, mult, seen)
                hbm += mult * fusion_touch_bytes(op, comp, comps)
            elif oc == "while":
                tm = _TRIP_RE.search(op.line)
                trips = int(tm.group(1)) if tm else 1
                if not tm:
                    unknown += 1
                for c in _called(op.line):
                    walk(c, mult * trips, seen | {name})
            elif oc in ("call", "conditional"):
                for c in _called(op.line):
                    walk(c, mult, seen | {name})
            elif any(oc.startswith(k) for k in COLLECTIVES):
                kind = next(k for k in COLLECTIVES if oc.startswith(k))
                if oc.endswith("-done"):
                    continue
                size = op.result_bytes
                if oc.endswith("-start") and op.line.count("[") > 1:
                    # start ops return (in, out [, context]) — use the last
                    shapes = _shape_bytes_list(
                        op.line[: op.line.index(oc + "(")])
                    size = shapes[-1] if shapes else size
                n, crosses = _group_info(op.line, n_devices, pod_size)
                w = mult * _wire_bytes(kind, size, n)
                wire += w
                by_kind[kind] = by_kind.get(kind, 0.0) + w
                if crosses:
                    dcn_b += w
                else:
                    ici_b += w
                n_coll += 1
            else:
                hbm += mult * top_level_bytes(op, comp, comps)

    walk(entry, 1.0, frozenset())
    return Analysis(flops, hbm, wire, by_kind, n_coll, unknown, ici_b, dcn_b)


def roofline_terms(analysis: Analysis) -> Dict:
    """Per-chip roofline terms in seconds. Collectives whose groups span
    pods cross the DCN (modeled at DCN_BW); the rest ride ICI."""
    compute_s = analysis.flops / PEAK_FLOPS
    memory_s = analysis.hbm_bytes / HBM_BW
    collective_s = (analysis.ici_bytes / (ICI_BW * ICI_LINKS)
                    + analysis.dcn_bytes / DCN_BW)
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "ici_bytes": analysis.ici_bytes,
        "dcn_bytes": analysis.dcn_bytes,
        "dominant": dominant,
        "bound_s": max(compute_s, memory_s, collective_s),
    }
