"""Parse compiled (post-SPMD) HLO text for roofline inputs.

Why not just ``compiled.cost_analysis()``?  Two reasons, both verified
empirically on this backend:
  1. cost_analysis counts while-loop bodies ONCE, ignoring trip counts —
     a scan-over-layers model reports 1/L of its true FLOPs;
  2. it reports nothing about collectives.

So the dry-run walks the HLO text itself:
  - split the module into computations; build a per-computation symbol
    table (op name -> result type), including computation parameters;
  - build the call graph (while body/condition with trip counts parsed
    from the loop-condition constant, fusion `calls=`, `to_apply=`) and
    resolve a transitive execution multiplier per computation;
  - FLOPs: every `dot` contributes 2 * prod(result_dims) * prod(lhs
    contracting dim sizes), scaled by the multiplier;
  - HBM traffic model: every materializing op (fusion/dot/copy/collective/
    gather/scatter/...) reads its operands and writes its result once;
  - collectives: result bytes -> wire bytes per device with ring formulas
    (all-gather (g-1)/g, all-reduce 2(g-1)/g, reduce-scatter (g-1),
    all-to-all (g-1)/g, permute 1), scaled by the multiplier; counted
    once per operand, so a tuple op from XLA's collective combiner
    counts as the collectives it combined.

Caveat (documented in EXPERIMENTS.md): the CPU backend upcasts bf16 dot
operands to f32 before compute and collectives, so byte counts here are a
<=2x-conservative proxy for the TPU bf16 program.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import lru_cache

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# HBM traffic model: WRITE-ONCE — every materializing op writes its result
# to HBM exactly once (reads are assumed amortized/fused; a read+write
# model double-counts every producer/consumer pair).  Layout-free ops
# (reshape/bitcast) and control ops are excluded.
_TRAFFIC_OPS = {
    "fusion", "dot", "copy", "convolution", "gather", "scatter",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "broadcast",
    "transpose", "reduce", "convert", "select", "pad", "slice", "sort",
    "rng-bit-generator", "cholesky", "triangular-solve", "custom-call",
} | set(_COLLECTIVES)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_START_RE = re.compile(r"^\s*(?:ENTRY\s+)?(%[\w\.\-]+)\s*\((.*)\)\s*->.*\{")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*((?:\([^=]*?\)|\S+?))\s+([a-z][\w\-]*)\("
)
_PARAM_RE = re.compile(r"(%?[\w\.\-]+):\s*((?:\w+\[[\d,]*\](?:\{[\d,]*\})?)|\w+\[\])")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shapes_in(type_str: str):
    return _SHAPE_RE.findall(type_str)


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _shapes_in(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _start_payload_bytes(type_str: str, num_operands: int) -> int:
    """Payload of an async collective ``*-start`` op.

    Its tuple type may carry, besides the results, the operand aliases
    and (on some backends) u32 context scalars — summing the whole tuple
    double-counts the payload.  Once the scalar contexts are dropped, the
    results are the last ``num_operands`` arrays (all of them where no
    alias is carried), and a combined op's payload is their sum."""
    arrays = [
        (dtype, dims) for dtype, dims in _shapes_in(type_str)
        if dtype in DTYPE_BYTES and not (dtype == "u32" and dims == "")
    ]
    return sum(
        _type_bytes(f"{dtype}[{dims}]") for dtype, dims in arrays[-num_operands:]
    )


def _dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _wire_bytes(op: str, result_bytes: int, g: int) -> float:
    if op == "all-gather":
        return result_bytes * (g - 1) / g
    if op == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return float(result_bytes * (g - 1))
    if op == "all-to-all":
        return result_bytes * (g - 1) / g
    return float(result_bytes)


def _operands(rest_of_line: str) -> list[str]:
    """Names inside the top-level parens starting at position 0."""
    depth = 0
    end = len(rest_of_line)
    for i, ch in enumerate(rest_of_line):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth < 0:
                end = i
                break
    return re.findall(r"%[\w\.\-]+", rest_of_line[:end])


@dataclass
class CollectiveOp:
    op: str
    computation: str
    result_bytes: int
    group_size: int
    multiplier: int = 1
    #: Arrays the op reduces or moves: XLA's collective combiners fuse
    #: several same-kind collectives into one tuple-shaped op, and each
    #: operand is still one logical collective.
    operands: int = 1

    @property
    def wire_bytes(self) -> float:
        return self.multiplier * _wire_bytes(self.op, self.result_bytes, self.group_size)


@dataclass
class ModuleAnalysis:
    flops: float = 0.0
    traffic_bytes: float = 0.0
    collectives: list[CollectiveOp] = field(default_factory=list)

    @property
    def collective_wire_bytes(self) -> float:
        return sum(o.wire_bytes for o in self.collectives)

    def collective_by_type(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for o in self.collectives:
            out[o.op] = out.get(o.op, 0.0) + o.wire_bytes
        return out

    def collective_counts(self) -> dict[str, int]:
        """Logical collectives per op kind, so a count does not depend on
        whether the compiler combined them (one per combined operand)."""
        out: dict[str, int] = {}
        for o in self.collectives:
            out[o.op] = out.get(o.op, 0) + o.multiplier * o.operands
        return out


def analyze_module(text: str) -> ModuleAnalysis:
    # ---- pass 1: computations, symbol tables, call edges -----------------
    comps: dict[str, list[str]] = {}
    symbols: dict[str, dict[str, str]] = {}
    current = "<module>"
    comps[current] = []
    symbols[current] = {}
    for line in text.splitlines():
        m = _COMP_START_RE.match(line)
        if m:
            current = m.group(1)
            comps.setdefault(current, [])
            symbols.setdefault(current, {})
            for pname, ptype in _PARAM_RE.findall(m.group(2)):
                symbols[current][pname.lstrip("%")] = ptype
            continue
        if line.strip() == "}":
            current = "<module>"
            continue
        comps.setdefault(current, []).append(line)
        om = _OP_RE.match(line)
        if om:
            symbols[current][om.group(1).lstrip("%")] = om.group(2)

    trip: dict[str, int] = {}
    parent: dict[str, str] = {}
    for comp, lines in comps.items():
        for line in lines:
            wm = re.search(r"condition=(%[\w\.\-]+),\s*body=(%[\w\.\-]+)", line)
            if not wm:
                wm = re.search(r"body=(%[\w\.\-]+),\s*condition=(%[\w\.\-]+)", line)
                if wm:
                    cond, body = wm.group(2), wm.group(1)
                else:
                    cond = body = None
            else:
                cond, body = wm.group(1), wm.group(2)
            if body:
                consts = [
                    int(c)
                    for l in comps.get(cond, [])
                    for c in _CONST_RE.findall(l)
                ]
                trip[body] = max(consts) if consts else 1
                parent[body] = comp
                parent[cond] = comp
            for cm in re.finditer(r"(?:calls|to_apply)=(%[\w\.\-]+)", line):
                parent.setdefault(cm.group(1), comp)

    # Fusion/reducer callees: their call site already accounts for the
    # operand/result traffic; only dot FLOPs inside them are counted.
    callee_set: set[str] = set()
    for comp, lines in comps.items():
        for line in lines:
            for cm in re.finditer(r"(?:calls|to_apply)=(%[\w\.\-]+)", line):
                callee_set.add(cm.group(1))

    @lru_cache(maxsize=None)
    def mult(comp: str) -> int:
        seen = set()
        total = 1
        c = comp
        while c in parent and c not in seen:
            seen.add(c)
            total *= trip.get(c, 1)
            c = parent[c]
        return total

    # ---- pass 2: flops / traffic / collectives ---------------------------
    out = ModuleAnalysis()
    for comp, lines in comps.items():
        m_comp = mult(comp)
        table = symbols[comp]
        for line in lines:
            om = _OP_RE.match(line)
            if not om:
                continue
            name, type_str, opcode = om.group(1), om.group(2), om.group(3)
            rest = line[om.end():]
            if opcode == "dot":
                ops = _operands(rest)
                lhs_type = table.get(ops[0].lstrip("%"), "") if ops else ""
                cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
                cdims = [int(d) for d in cm.group(1).split(",") if d] if cm else []
                ldims = _dims(lhs_type)
                k = 1
                for cd in cdims:
                    if cd < len(ldims):
                        k *= ldims[cd]
                rdims = _dims(type_str)
                r = 1
                for d in rdims:
                    r *= d
                out.flops += 2.0 * r * k * m_comp
            if comp in callee_set:
                continue  # traffic/collectives counted at the call site
            # Async collectives lower as `<op>-start` / `<op>-done`
            # pairs; count the start (it names the payload) under the
            # base opcode so overlapped collectives are never missed,
            # and skip the matching done (it would double-count).
            base_op = opcode[: -len("-start")] if opcode.endswith("-start") else opcode
            if base_op in _COLLECTIVES and not opcode.endswith("-done"):
                n_ops = max(1, len(_operands(rest)))
                rb = (
                    _start_payload_bytes(type_str, n_ops)
                    if opcode.endswith("-start")
                    else _type_bytes(type_str)
                )
                if rb:
                    out.collectives.append(
                        CollectiveOp(
                            op=base_op, computation=comp, result_bytes=rb,
                            group_size=_group_size(line), multiplier=m_comp,
                            operands=n_ops,
                        )
                    )
            if opcode in _TRAFFIC_OPS:
                out.traffic_bytes += _type_bytes(type_str) * m_comp
    return out


# Backwards-compatible helper used by tests.
def parse_collectives(text: str):
    analysis = analyze_module(text)

    class _Report:
        ops = analysis.collectives
        total_wire_bytes = analysis.collective_wire_bytes

        @staticmethod
        def by_type():
            return analysis.collective_by_type()

        @staticmethod
        def counts():
            return analysis.collective_counts()

    return _Report()
