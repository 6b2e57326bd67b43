"""Trip-count-aware cost model over post-optimization HLO text.

XLA's HloCostAnalysis counts a while-loop body ONCE, but jax lowers
lax.scan to while — so for a 60-layer scanned transformer the built-in
cost_analysis() under-reports FLOPs/bytes/collectives by ~60x (verified
empirically; see EXPERIMENTS.md §Dry-run notes). This module re-derives

    flops              dots (2*M*N*K) + elementwise/transcendental (1/elem)
    hbm bytes          operand+result sizes of materializing top-level ops
                       (fusion boundaries = buffer materialization points)
    collective ops     (kind, result bytes, replica-group size) x multiplier

by walking the HLO call graph and MULTIPLYING while bodies by their trip
counts (parsed from the loop-condition constant). Costs are per-device —
the text is the post-SPMD module.

This is a deliberately simple model: bitcasts/reshapes/tuples are free,
fusions count their operands+outputs as HBM traffic and their interior
elementwise work as flops. Good to ~10-20% vs the built-in analysis on
loop-free programs (tested in tests/test_hlo_cost.py).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


def normalize_cost_analysis(ca) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict: ``{}`` on a backend
    without a cost model, where it returns ``None``."""
    return dict(ca) if ca else {}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_ELEMENTWISE_1 = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "and",
    "or", "xor", "negate", "abs", "compare", "select", "clamp", "floor",
    "ceil", "round-nearest-afz", "sign", "not",
}
_TRANSCENDENTAL = {
    "exponential", "log", "tanh", "rsqrt", "sqrt", "power", "logistic",
    "sine", "cosine", "exponential-minus-one", "log-plus-one", "atan2",
    "erf", "cbrt",
}
# HBM-traffic model, two tiers (EXPERIMENTS.md §Dry-run notes):
#
# _MATERIALIZING ("perfect-fusion" / dot-centric, the headline number):
#   tensors crossing compute/reorder/collective boundaries — dot operands
#   and results, cache updates, gathers/scatters, sorts, collectives. This
#   approximates a well-fused TPU program where elementwise chains stay in
#   VMEM/registers. Top-level convert/copy/broadcast and *fusion outputs*
#   are excluded: on this CPU backend they are bf16-normalization and
#   fusion-granularity artifacts (measured 10-50x inflation vs TPU-plausible
#   traffic when included).
# _MATERIALIZING_UPPER adds fusion-boundary I/O — a conservative upper
#   bound reported alongside as bytes_upper.
_MATERIALIZING = {
    "dot", "dynamic-update-slice", "dynamic-slice",
    "convolution", "gather", "scatter", "reduce", "sort",
    "concatenate", "pad", "reduce-window",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "rng", "select-and-scatter",
    "cholesky", "triangular-solve",
}
_MATERIALIZING_UPPER = _MATERIALIZING | {"fusion"}
_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
# first lowercase word( in the RHS is the op kind; everything before it is
# the (possibly tuple, possibly /*index=N*/-commented) result type
_KIND_RE = re.compile(r"(?:^|[\s/])([a-z][\w\-]*)\(")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')
_CALLS_RE = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_shapes(type_str: str) -> list[tuple[str, str]]:
    return _SHAPE_RE.findall(type_str)


def _type_bytes(type_str: str) -> int:
    return sum(
        _shape_elems(dims) * _DTYPE_BYTES.get(dt, 4)
        for dt, dims in _first_shapes(type_str)
    )


@dataclass
class Op:
    name: str
    kind: str
    result_type: str
    rest: str  # args + attributes, raw


@dataclass
class Computation:
    name: str
    ops: list[Op] = field(default_factory=list)


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    current: Computation | None = None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and "->" in line and ("%" in line or line.lstrip().startswith("ENTRY")):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                current = Computation(m.group(1))
                comps[current.name] = current
                continue
        if line.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        m = _ASSIGN_RE.match(line)
        if m:
            name, rhs = m.groups()
            km = _KIND_RE.search(rhs)
            if not km:
                continue
            kind = km.group(1)
            rtype = rhs[: km.start()].strip()
            rest = rhs[km.end():]
            current.ops.append(Op(name, kind, rtype, rest))
    return comps


class CostModel:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        # symbol table: op name -> result type (for operand byte lookup)
        self.types: dict[str, str] = {}
        self.consts: dict[str, int] = {}
        for comp in self.comps.values():
            for op in comp.ops:
                self.types[op.name] = op.result_type
                if op.kind == "constant" and op.result_type.startswith("s32[]"):
                    cm = re.match(r"(\d+)", op.rest)
                    if cm:
                        self.consts[op.name] = int(cm.group(1))
        self._memo: dict[str, tuple[float, float, list]] = {}
        self.entry = self._find_entry(text)

    def _find_entry(self, text: str) -> str:
        m = re.search(r"ENTRY\s+%?([\w.\-]+)", text)
        return m.group(1) if m else next(iter(self.comps))

    # ------------------------------------------------------------- pieces

    def _trip_count(self, cond_name: str) -> int:
        comp = self.comps.get(cond_name)
        if not comp:
            return 1
        best = 1
        for op in comp.ops:
            if op.kind == "compare":
                for ref in _OPERAND_RE.findall(op.rest):
                    if ref in self.consts:
                        best = max(best, self.consts[ref])
            if op.kind == "constant" and op.result_type.startswith("s32[]"):
                cm = re.match(r"(\d+)", op.rest)
                if cm:
                    best = max(best, int(cm.group(1)))
        return best

    def _dot_flops(self, op: Op) -> float:
        out_elems = sum(_shape_elems(d) for _, d in _first_shapes(op.result_type))
        m = _LHS_CONTRACT_RE.search(op.rest)
        k = 1
        if m:
            # lhs operand type = first shape among the args
            args_part = op.rest.split("),")[0]
            lhs_ref = _OPERAND_RE.search(args_part)
            if lhs_ref and lhs_ref.group(1) in self.types:
                lhs_shapes = _first_shapes(self.types[lhs_ref.group(1)])
                if lhs_shapes:
                    dims = [int(x) for x in lhs_shapes[0][1].split(",") if x]
                    for idx in m.group(1).split(","):
                        if idx and int(idx) < len(dims):
                            k *= dims[int(idx)]
        return 2.0 * out_elems * k

    def _op_bytes(self, op: Op) -> float:
        # In-place/indexed ops: count only the data actually moved, not the
        # whole buffer — XLA aliases DUS in place (we donate caches), and a
        # gather reads |result| rows, not the table. Without this the scan
        # plumbing of a 60-layer KV cache shows up as 2.5 TB/step (measured).
        kind = op.kind
        if kind in ("dynamic-slice", "gather"):
            return float(_type_bytes(op.result_type))
        if kind in ("dynamic-update-slice", "scatter"):
            ops_ = _OPERAND_RE.findall(
                op.rest.split(", calls=")[0].split(", body=")[0]
            )
            if len(ops_) >= 2 and ops_[1] in self.types:
                return 2.0 * _type_bytes(self.types[ops_[1]])  # read+write slot
            return float(_type_bytes(op.result_type))
        total = _type_bytes(op.result_type)
        # operands: look up each referenced symbol once
        for ref in _OPERAND_RE.findall(op.rest.split(", calls=")[0].split(", body=")[0]):
            if ref in self.types:
                total += _type_bytes(self.types[ref])
        return float(total)

    def _collective(self, op: Op) -> dict:
        nbytes = _type_bytes(op.result_type)
        gm = _GROUPS_RE.search(op.rest)
        if gm:
            group = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(op.rest)
            group = len(gb.group(1).split(",")) if gb else None
        return {"kind": op.kind.replace("-start", ""), "bytes": nbytes, "group_size": group}

    # ------------------------------------------------------------- walk

    def cost(self, comp_name: str | None = None) -> tuple[float, float, float, list]:
        """Returns (flops, hbm_bytes, hbm_bytes_upper, collectives list) for
        a computation, while bodies multiplied by trip count."""
        comp_name = comp_name or self.entry
        if comp_name in self._memo:
            return self._memo[comp_name]
        comp = self.comps.get(comp_name)
        if comp is None:
            return (0.0, 0.0, 0.0, [])
        flops = 0.0
        bytes_ = 0.0
        bytes_up = 0.0
        colls: list[dict] = []
        self._memo[comp_name] = (0.0, 0.0, 0.0, [])  # cycle guard
        for op in comp.ops:
            kind = op.kind.replace("-start", "")
            if kind == "while":
                body = _CALLS_RE.search(op.rest)
                tm = _TRIP_RE.search(op.rest)  # XLA annotates known trip counts
                if tm:
                    trips = int(tm.group(1))
                else:
                    cond = _COND_RE.search(op.rest)
                    trips = self._trip_count(cond.group(1)) if cond else 1
                if body:
                    bf, bb, bu, bc = self.cost(body.group(1))
                    flops += trips * bf
                    bytes_ += trips * bb
                    bytes_up += trips * bu
                    for c in bc:
                        colls.append({**c, "count_mult": trips * c.get("count_mult", 1)})
                continue
            if kind in ("call", "conditional"):
                for ref in _CALLS_RE.findall(op.rest):
                    cf, cb, cu, cc = self.cost(ref)
                    flops += cf
                    bytes_ += cb
                    bytes_up += cu
                    colls.extend(cc)
                continue
            if kind == "fusion":
                body = _CALLS_RE.search(op.rest)
                if body:
                    cf, cb, cu, cc = self.cost(body.group(1))
                    flops += cf           # interior arithmetic
                    bytes_ += cb          # dots/gathers inside the fusion
                    bytes_up += cu
                    colls.extend(cc)
                bytes_up += self._op_bytes(op)  # fusion-boundary I/O (upper tier)
                continue
            if kind in _COLLECTIVES:
                colls.append(self._collective(op))
                bytes_ += self._op_bytes(op)
                bytes_up += self._op_bytes(op)
                continue
            if kind == "dot":
                flops += self._dot_flops(op)
                bytes_ += self._op_bytes(op)
                bytes_up += self._op_bytes(op)
                continue
            if kind == "convolution":
                # rough: 2 * out_elems * (kernel window size); fall back to bytes
                flops += 2.0 * _type_bytes(op.result_type)
                bytes_ += self._op_bytes(op)
                bytes_up += self._op_bytes(op)
                continue
            elems = sum(_shape_elems(d) for _, d in _first_shapes(op.result_type))
            if kind in _TRANSCENDENTAL:
                flops += 4.0 * elems  # transcendental weight
            elif kind in _ELEMENTWISE_1 or kind in ("reduce", "reduce-window"):
                flops += float(elems)
            if kind in _MATERIALIZING:
                bytes_ += self._op_bytes(op)
            if kind in _MATERIALIZING_UPPER:
                bytes_up += self._op_bytes(op)
        result = (flops, bytes_, bytes_up, colls)
        self._memo[comp_name] = result
        return result


def analyze_text(text: str) -> dict:
    cm = CostModel(text)
    flops, bytes_, bytes_up, colls = cm.cost()
    expanded = []
    for c in colls:
        mult = c.pop("count_mult", 1)
        expanded.append({**c, "count": mult, "total_bytes": c["bytes"] * mult})
    agg: dict[str, dict] = {}
    for c in expanded:
        a = agg.setdefault(c["kind"], {"count": 0, "bytes": 0.0})
        a["count"] += c["count"]
        a["bytes"] += c["total_bytes"]
    return {
        "flops": flops,
        "bytes_accessed": bytes_,
        "bytes_upper": bytes_up,
        "collective_ops": expanded,
        "collectives": agg,
    }
