"""Loop-aware analysis of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts a while-loop body ONCE, which silently
drops ~L x the FLOPs of a scan-over-layers program (verified in
tests/test_hlo_analysis.py).  This walker parses ``compiled.as_text()`` and
multiplies每 computation by its executed trip count:

* ``while`` ops carry ``backend_config={"known_trip_count":{"n":"48"}}``;
* ``fusion`` / ``call`` / ``conditional`` recurse into their called
  computations (conditional = max over branches);
* ``dot`` FLOPs = 2 * prod(result dims) * prod(contracted dims);
* per-instruction HBM traffic = result bytes + operand bytes at fusion
  granularity (XLA's own memory model: fusions stream operands/outputs);
* collectives (incl. ``-start`` forms) are tallied by kind and bytes.

Everything is per-device (the HLO is the per-device SPMD program).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[^=]*?)\s*([a-z][\w\-]*)\((.*)$")
_CALLS_RE = re.compile(r"(?:calls=|to_apply=|body=|condition=)%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# opcodes that are pure bookkeeping: no flops, no HBM traffic of their own
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "partition-id", "replica-id", "iota",
             "get-dimension-size", "opt-barrier", "domain"}

# ~1 flop per output element
_ELEMENTWISE_HINT = {"add", "subtract", "multiply", "divide", "maximum",
                     "minimum", "exponential", "log", "tanh", "rsqrt",
                     "sqrt", "negate", "abs", "power", "compare", "select",
                     "and", "or", "xor", "convert", "floor", "ceil",
                     "cosine", "sine", "logistic", "reduce", "clamp"}


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as floats; ``{}`` for a program XLA
    gives no analysis (``None``)."""
    return {k: float(v) for k, v in (cost or {}).items()}


def shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_elems(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n
    return total


def _dims_of(txt: str) -> List[List[int]]:
    """All array shapes appearing in a type string."""
    out = []
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        out.append([int(d) for d in dims.split(",") if d] or [1])
    return out


@dataclass
class Instr:
    name: str
    opcode: str
    type_txt: str
    args_txt: str
    result_bytes: int
    operands: List[str]
    calls: List[str]
    trip_count: int = 1
    branches: List[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    by_name: Dict[str, Instr] = field(default_factory=dict)
    params: Dict[int, Instr] = field(default_factory=dict)


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    comment_re = re.compile(r"/\*.*?\*/")
    for raw in text.splitlines():
        line = comment_re.sub("", raw).rstrip()
        if not line:
            continue
        hdr = _COMP_HDR_RE.match(line.strip())
        if hdr and line.strip().endswith("{"):
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.strip().startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_txt, opcode, rest = m.groups()
        # split args from attrs at the matching close paren
        depth, idx = 1, 0
        for idx, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        args_txt = rest[:idx]
        attrs_txt = rest[idx:]
        instr = Instr(
            name=name,
            opcode=opcode,
            type_txt=type_txt,
            args_txt=args_txt,
            result_bytes=shape_bytes(type_txt),
            operands=_OPERAND_RE.findall(args_txt),
            calls=_CALLS_RE.findall(attrs_txt),
        )
        bm = _BRANCHES_RE.search(attrs_txt)
        if bm:
            instr.branches = _OPERAND_RE.findall(bm.group(1))
        if opcode == "while":
            tm = _TRIP_RE.search(attrs_txt)
            instr.trip_count = int(tm.group(1)) if tm else 1
        if opcode == "parameter":
            try:
                cur.params[int(args_txt.strip())] = instr
            except ValueError:
                pass
        cur.instrs.append(instr)
        cur.by_name[name] = instr
    return comps, entry


def _dot_flops(instr: Instr, comp: Computation,
               comps: Dict[str, Computation]) -> float:
    """2 * prod(result dims) * prod(contracting dims of lhs)."""
    result_dims = _dims_of(instr.type_txt)
    out_elems = 1
    for d in (result_dims[0] if result_dims else [1]):
        out_elems *= d
    # lhs shape from the operand's defining instruction
    lhs_shape: List[int] = []
    if instr.operands:
        lhs = comp.by_name.get(instr.operands[0])
        if lhs is not None:
            ds = _dims_of(lhs.type_txt)
            if ds:
                lhs_shape = ds[0]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.args_txt)
    if not m:  # attrs may sit beyond args split; search the full line parts
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}",
                      instr.args_txt + instr.type_txt)
    contract = 1
    if m and lhs_shape:
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_shape):
                contract *= lhs_shape[int(d)]
    elif lhs_shape:
        contract = lhs_shape[-1]
    return 2.0 * out_elems * contract


@dataclass
class Analysis:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collectives: Dict[str, Dict[str, float]] = field(default_factory=dict)

    def add(self, other: "Analysis", factor: float = 1.0) -> None:
        self.flops += other.flops * factor
        self.bytes += other.bytes * factor
        self.collective_bytes += other.collective_bytes * factor
        for k, v in other.collectives.items():
            slot = self.collectives.setdefault(k, {"count": 0, "bytes": 0.0})
            slot["count"] += v["count"] * factor
            slot["bytes"] += v["bytes"] * factor


# ops that read only their (small) result-sized window of a big operand
_SLICING_OPS = {"dynamic-slice", "slice", "gather"}


def _operand_bytes(instr: Instr, comp: Computation,
                   comps: Optional[Dict[str, Computation]] = None) -> int:
    """Effective bytes read from operands.

    For fusions, an operand whose only in-fusion users are slicing ops is
    charged at the slice size, not the full array - otherwise a scan that
    dynamic-slices its stacked layer weights would be charged L x the whole
    stack per iteration."""
    called = None
    if comps is not None and instr.opcode == "fusion" and instr.calls:
        called = comps.get(instr.calls[0])
    total = 0
    for i, op in enumerate(instr.operands):
        d = comp.by_name.get(op)
        if d is None or d.opcode == "constant":
            continue
        full = d.result_bytes
        if called is not None:
            par = called.params.get(i)
            if par is not None:
                users = [u for u in called.instrs
                         if par.name in u.operands]
                if users and all(u.opcode in _SLICING_OPS for u in users):
                    full = min(full, sum(u.result_bytes for u in users))
        total += full
    return total


def analyze_computation(name: str, comps: Dict[str, Computation],
                        cache: Dict[Tuple[str, bool], Analysis],
                        count_bytes: bool = True) -> Analysis:
    """Cost of one executed pass through computation ``name``.

    ``count_bytes=False`` is used inside fusions: inner ops contribute FLOPs
    but no HBM traffic (the fusion boundary is charged by the caller)."""
    key = (name, count_bytes)
    if key in cache:
        return cache[key]
    comp = comps.get(name)
    out = Analysis()
    cache[key] = out
    if comp is None:
        return out
    for ins in comp.instrs:
        op = ins.opcode
        if op in _FREE_OPS:
            continue
        is_coll = any(op.startswith(c) for c in COLLECTIVES)
        if is_coll:
            if op.endswith("-done"):
                continue
            kind = next(c for c in COLLECTIVES if op.startswith(c))
            b = ins.result_bytes
            slot = out.collectives.setdefault(kind,
                                              {"count": 0, "bytes": 0.0})
            slot["count"] += 1
            slot["bytes"] += b
            out.collective_bytes += b
            continue
        if op == "while":
            inner = Analysis()
            for c in ins.calls:  # condition + body
                inner.add(analyze_computation(c, comps, cache, count_bytes))
            out.add(inner, ins.trip_count)
            continue
        if op == "conditional":
            branches = ins.branches or ins.calls
            if branches:
                sub = [analyze_computation(b, comps, cache, count_bytes)
                       for b in branches]
                # execution takes one branch: charge the max-cost branch
                out.add(max(sub, key=lambda a: a.flops + a.bytes))
            continue
        if op == "fusion":
            for c in ins.calls:
                out.add(analyze_computation(c, comps, cache, False))
            if count_bytes:
                out.bytes += ins.result_bytes + _operand_bytes(ins, comp,
                                                               comps)
            continue
        if op in ("call", "async-start"):
            for c in ins.calls:
                out.add(analyze_computation(c, comps, cache, count_bytes))
            continue
        if op in _SLICING_OPS:
            if count_bytes:
                out.bytes += 2 * ins.result_bytes  # read slice + write
            continue
        if op == "dynamic-update-slice":
            if count_bytes:
                upd = (comp.by_name.get(ins.operands[1])
                       if len(ins.operands) > 1 else None)
                out.bytes += 2 * (upd.result_bytes if upd is not None
                                  else ins.result_bytes)
            continue
        if op == "scatter":
            if count_bytes and len(ins.operands) > 2:
                upd = comp.by_name.get(ins.operands[2])
                out.bytes += 2 * (upd.result_bytes if upd is not None
                                  else ins.result_bytes)
            continue
        if op == "dot":
            out.flops += _dot_flops(ins, comp, comps)
            if count_bytes:
                out.bytes += ins.result_bytes + _operand_bytes(ins, comp)
            continue
        if op == "convolution":
            out_elems = shape_elems(ins.type_txt)
            ker = 1
            if len(ins.operands) > 1:
                kd = comp.by_name.get(ins.operands[1])
                if kd is not None:
                    ds = _dims_of(kd.type_txt)
                    if ds:
                        for d in ds[0]:
                            ker *= d
            out.flops += 2.0 * out_elems * ker
            if count_bytes:
                out.bytes += ins.result_bytes + _operand_bytes(ins, comp)
            continue
        # default: elementwise / data-movement ops
        if op in _ELEMENTWISE_HINT:
            out.flops += shape_elems(ins.type_txt)
        if count_bytes:
            out.bytes += ins.result_bytes + _operand_bytes(ins, comp)
    return out


def analyze_hlo(text: str) -> Dict:
    comps, entry = parse_hlo(text)
    cache: Dict[Tuple[str, bool], Analysis] = {}
    # fusions/whiles are reachable from ENTRY; computations referenced via
    # calls are consumed there - analyze ENTRY only.
    if entry is None:
        # fall back: largest computation
        entry = max(comps, key=lambda c: len(comps[c].instrs)) if comps else ""
    a = analyze_computation(entry, comps, cache)
    return {
        "flops": a.flops,
        "bytes": a.bytes,
        "collective_bytes": a.collective_bytes,
        "collectives": {k: {"count": int(v["count"]),
                            "bytes": float(v["bytes"])}
                        for k, v in a.collectives.items()},
        "entry": entry,
        "n_computations": len(comps),
    }
