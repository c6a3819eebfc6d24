"""Static analyzer for compiled (post-optimization) HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE — for scan-
over-layers models this under-counts FLOPs/bytes/collectives by the layer
count. This module re-derives loop-corrected aggregates directly from
``compiled.as_text()``:

  * per-computation instruction parse (name -> shape(s), op, operands,
    attributes),
  * dot FLOPs from result shape × contracting dims (operand shapes come
    from the computation-local symbol table),
  * HBM-traffic model: operands+result bytes for memory-touching ops
    (fusion boundaries = HBM round-trips; fusion internals are free,
    matching how XLA:TPU stages through VMEM),
  * collective wire bytes by kind (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute),
  * while-loop expansion: body cost × trip count (trip count parsed from
    the loop-condition constant — scan-generated loops always compare a
    counter against a literal).

This is the "profile" the §Perf iterations read, since no real TPU
timeline exists on this host (DESIGN.md D1).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops whose operands+result count as HBM traffic (fusion boundaries)
_MEM_OPS = {"fusion", "dot", "custom-call", "copy", "scatter", "gather",
            "dynamic-slice", "dynamic-update-slice", "reduce", "sort",
            "convolution", "concatenate", "slice", "pad", "reduce-window",
            "select-and-scatter", "broadcast", "transpose", "reshape",
            "iota", "add", "multiply", "select", "compare", "exponential",
            "tanh", "divide", "subtract", "maximum", "minimum", "rsqrt",
            "convert"} | set(COLLECTIVES)

_SHAPE_TOKEN = re.compile(r"(\w+)\[([\d,]*)\](?:\{[^}]*\})?")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s*"
    r"([a-z][a-z0-9\-]*)\((.*)$")
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_TOKEN.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> list[list[int]]:
    out = []
    for m in _SHAPE_TOKEN.finditer(type_str):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append(dims)
    return out


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    args: str            # raw text after the opening paren

    @property
    def result_bytes(self) -> int:
        return _shape_bytes(self.type_str)


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list
    symbols: dict        # name -> type_str


@dataclasses.dataclass
class Aggregate:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    collective_counts: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "Aggregate", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0) \
                + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) \
                + v * mult

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def parse_module(text: str) -> dict:
    """-> {computation_name: Computation}; last ENTRY is named in
    result['__entry__'] (stored as a Computation-name string)."""
    comps: dict = {}
    entry = None
    cur: Optional[Computation] = None
    for line in text.splitlines():
        mh = _COMP_HEADER.match(line)
        if mh and ("->" in line):
            cur = Computation(mh.group(1), [], {})
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, type_str, op, args = mi.groups()
        cur.symbols[name] = type_str
        cur.instrs.append(Instr(name, type_str, op, args))
    comps["__entry__"] = entry
    return comps


_CALLED = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)"
                     r"=\{?%?([\w.\-]+)")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_TRIP_CONST = re.compile(r"constant\((\d+)\)")


def _dot_flops(instr: Instr, symbols: dict) -> float:
    dims_out = _shape_dims(instr.type_str)
    out_elems = 1
    for d in (dims_out[0] if dims_out else []):
        out_elems *= d
    mc = _CONTRACT.search(instr.args)
    contract = 1
    ops = _OPERANDS.findall(instr.args.split(")")[0])
    if mc and ops:
        lhs_type = symbols.get(ops[0], "")
        lhs_dims = _shape_dims(lhs_type)
        if lhs_dims:
            for idx_s in mc.group(1).split(","):
                if idx_s and int(idx_s) < len(lhs_dims[0]):
                    contract *= lhs_dims[0][int(idx_s)]
    return 2.0 * out_elems * contract


def _operand_bytes_list(instr: Instr, symbols: dict) -> list[int]:
    head = instr.args.split("),")[0]
    out = []
    for name in _OPERANDS.findall(head):
        t = symbols.get(name)
        if t:
            out.append(_shape_bytes(t))
    return out


def _operand_bytes(instr: Instr, symbols: dict) -> int:
    return sum(_operand_bytes_list(instr, symbols))


def _dus_bytes(instr: Instr, symbols: dict) -> int:
    """HBM traffic of a dynamic-update-slice: XLA aliases the target
    buffer in place, so only the UPDATE slice is read+written — counting
    the full buffer per scan step inflated memory terms ~30x (the bug
    that produced a 92 PB 'measurement'; EXPERIMENTS.md §Perf A1-note)."""
    ops = _operand_bytes_list(instr, symbols)
    if not ops:
        return instr.result_bytes
    update = sum(ops) - max(ops)     # everything but the aliased target
    return 2 * update


def _fusion_root_op(comps: dict, called: str) -> str:
    comp = comps.get(called)
    if comp is None or not comp.instrs:
        return ""
    return comp.instrs[-1].op


def _shape_bytes_list(type_str: str) -> list[int]:
    out = []
    for m in _SHAPE_TOKEN.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dt])
    return out


def _inplace_fusion_bytes(ins: Instr, comp: Computation,
                          comps: dict, called: str) -> int:
    """HBM traffic of a loop-carrier fusion (root = dynamic-update-slice
    or a tuple of them): carried buffers are aliased in place by XLA, so
    an operand whose size matches a result element is free; the actual
    traffic is the slice updates (2x update size) plus unaliased
    operands/results."""
    ops = _operand_bytes_list(ins, comp.symbols)
    res = _shape_bytes_list(ins.type_str)
    ops_left = sorted(ops, reverse=True)
    unmatched_res = 0
    for r in sorted(res, reverse=True):
        if r in ops_left:
            ops_left.remove(r)           # aliased carry: free
        else:
            unmatched_res += r
    total = unmatched_res + sum(ops_left)
    # slice updates inside the fused computation
    sub = comps.get(called)
    if sub is not None:
        for si in sub.instrs:
            if si.op == "dynamic-update-slice":
                total += _dus_bytes(si, sub.symbols)
            elif si.op == "dynamic-slice":
                total += 2 * si.result_bytes
    return total


def _trip_count(cond: Computation) -> int:
    """Loop bound = the largest integer literal in the condition."""
    best = 1
    for ins in cond.instrs:
        line = f"{ins.op}({ins.args}"
        for m in _TRIP_CONST.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def analyze_computation(comps: dict, name: str, memo: dict,
                        stack=()) -> Aggregate:
    if name in memo:
        return memo[name]
    if name in stack or name not in comps:
        return Aggregate()
    comp = comps[name]
    agg = Aggregate()
    for ins in comp.instrs:
        if ins.op in COLLECTIVES or \
                any(ins.op == c + "-start" for c in COLLECTIVES):
            kind = ins.op.replace("-start", "")
            agg.collective_bytes[kind] = \
                agg.collective_bytes.get(kind, 0) + ins.result_bytes
            agg.collective_counts[kind] = \
                agg.collective_counts.get(kind, 0) + 1
            agg.hbm_bytes += ins.result_bytes
            continue
        if ins.op == "while":
            called = dict.fromkeys(_CALLED.findall(ins.args))
            body = cond = None
            mb = re.search(r"body=%?([\w.\-]+)", ins.args)
            mc = re.search(r"condition=%?([\w.\-]+)", ins.args)
            body = mb.group(1) if mb else None
            cond = mc.group(1) if mc else None
            trips = _trip_count(comps[cond]) if cond in comps else 1
            if body in comps:
                agg.add(analyze_computation(comps, body, memo,
                                            stack + (name,)), trips)
            continue
        if ins.op in ("call", "conditional"):
            for cn in _CALLED.findall(ins.args):
                agg.add(analyze_computation(comps, cn, memo,
                                            stack + (name,)))
            continue
        if ins.op == "fusion":
            mcall = re.search(r"calls=%?([\w.\-]+)", ins.args)
            called = mcall.group(1) if mcall else ""
            root = _fusion_root_op(comps, called)
            if root in ("dynamic-update-slice", "tuple"):
                agg.hbm_bytes += _inplace_fusion_bytes(ins, comp, comps,
                                                       called)
            elif root == "dynamic-slice":
                agg.hbm_bytes += 2 * ins.result_bytes
            else:
                agg.hbm_bytes += ins.result_bytes + _operand_bytes(
                    ins, comp.symbols)
            if called in comps:
                # fused dots still burn MXU flops; fused bytes are free
                sub = analyze_computation(comps, called, memo,
                                          stack + (name,))
                agg.flops += sub.flops
            continue
        if ins.op == "dot":
            agg.flops += _dot_flops(ins, comp.symbols)
            agg.hbm_bytes += ins.result_bytes + _operand_bytes(
                ins, comp.symbols)
            continue
        if ins.op == "dynamic-update-slice":
            agg.hbm_bytes += _dus_bytes(ins, comp.symbols)
            continue
        if ins.op == "dynamic-slice":
            agg.hbm_bytes += 2 * ins.result_bytes
            continue
        if ins.op in _MEM_OPS:
            agg.hbm_bytes += ins.result_bytes + _operand_bytes(
                ins, comp.symbols)
    memo[name] = agg
    return agg


def analyze(text: str) -> Aggregate:
    comps = parse_module(text)
    entry = comps.pop("__entry__", None)
    memo: dict = {}
    if entry is None:
        # fall back: largest computation
        entry = max((c for c in comps), key=lambda c: len(comps[c].instrs))
    # note: fused-computation flops are also reachable directly; memoized
    # analysis from entry only visits what executes.
    return analyze_computation(comps, entry, memo)


# -- metadata -----------------------------------------------------------

_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# Tables XLA prints after the module header for source locations.
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def strip_metadata(text: str) -> str:
    """Compiled HLO text without what only describes where an op came
    from: every ``metadata={...}`` attribute (op names from
    ``jax.named_scope`` and the tracing path, source lines, stack-frame
    ids) and the stack-frame tables.  Two programs that differ only in
    names and source locations strip to the same text."""
    out, skipping = [], False
    for line in text.splitlines():
        if line.strip() in _FRAME_TABLES:
            skipping = True
        elif skipping:
            skipping = bool(line.strip())      # a table ends at a blank
        elif line.strip() or (out and out[-1]):
            out.append(_METADATA.sub("", line))
    return "\n".join(out)


_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def scope_path(op_name: str) -> str:
    """An ``op_name`` with JAX's transformation wrappers taken off each
    component: ``jit(f)/transpose(jvp(aggregate))/bucket[1]/add`` ->
    ``jit(f)/aggregate/bucket[1]/add``.  A scope entered inside a
    transformed function (a custom_vjp backward, say) keeps its name
    this way."""
    parts = []
    for part in op_name.split("/"):
        m = _TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        parts.append(part)
    return "/".join(parts)


def scope_collective_bytes(text: str, scope: str,
                           kind: str = "collective-permute") -> int:
    """Result bytes of the ``kind`` collectives (synchronous or
    ``-start``) whose :func:`scope_path` holds ``scope`` as whole path
    components, e.g. ``scope="bucket[0]/stage[1]"``.  Counted once per
    instruction, as :func:`analyze` counts collectives outside loops."""
    comps = parse_module(text)
    comps.pop("__entry__", None)
    want = "/" + scope + "/"
    total = 0
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op not in (kind, kind + "-start"):
                continue
            m = _OP_NAME.search(ins.args)
            if m and want in "/" + scope_path(m.group(1)) + "/":
                total += ins.result_bytes
    return total
