"""From a profiler trace (``.xplane.pb``) of a few training steps to
device intervals: busy time, matmul time, collective time and the part
of it no compute hides, and the idle gaps with what the host was doing.

A TPU's plane (``/device:TPU:<n>``) has two lines that matter:

  ``XLA Modules``    one event per program execution;
  ``XLA Ops``        one event per HLO instruction executed, named by
                     its HLO text (``%fusion.12 = bf16[...] fusion(...)``);
                     a ``while`` op spans its whole loop, its body's ops
                     appear beside it; an asynchronous collective shows as
                     its ``-start`` and its ``-done`` op.

A collective is in flight from the start of its ``-start`` op to the end
of the ``-done`` op that waits on it; the pairs are read from the HLO.
(Only the first device's plane has an ``Async XLA Ops`` line, so it is
not used.)  What an instruction does (matmul, collective, loop) and the
FLOPs of its dots and convolutions are read from the compiled program's
HLO text, which names the same instructions; an op executed in a loop
appears once per iteration, so summing over the trace's events counts
the work the device did.  Host threads (``/host:CPU``) carry the harness's
``TraceAnnotation`` spans.
"""
from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")
MATMUL_OPS = ("convolution", "dot")
# Ops whose called computations are part of the op itself.
WRAPPERS = ("fusion", "async-start", "async-update", "async-done")

_NAME = re.compile(r"^%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|"
                    r"async_execution_thread_computation)=%?([\w.\-]+)")
_CALLS_LIST = re.compile(r"calls=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[([\d,]*)\]")
_LABELS = re.compile(r"dim_labels=([0-9a-z]+)_([0-9a-z]+)->([0-9a-z]+)")
_WINDOW = re.compile(r"window=\{([^}]*)\}")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def _opcode_at(rest: str) -> tuple[str, int]:
    """The opcode of an instruction's right-hand side and where its
    operand list opens: the first ``word(`` after the result type, which
    may itself hold parens."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif depth == 0 and ch == " ":
            m = _OPCODE.match(rest[i:])
            if m:
                return m.group(1), i + m.end()
    m = re.match(r"([a-z][a-z0-9\-]*)\(", rest)
    return (m.group(1), m.end()) if m else ("", 0)


def _operands(rest: str, open_at: int) -> tuple[list, str]:
    """(operand names, the attributes after the list) of the operand
    list that opens at ``open_at``."""
    depth = 1
    for j in range(open_at, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(rest[open_at:j]), rest[j + 1:]
    return _OPERAND.findall(rest[open_at:]), ""


def parse_hlo(text: str) -> dict:
    """Instruction name -> {"opcode", "type", "operands", "attrs",
    "calls", "op_name"} over every computation of a compiled module's
    text (names are unique in a module), and computation -> its
    instructions."""
    instrs = {}
    comps: dict[str, list[str]] = {}
    current = None
    for line in text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _COMP.match(line)
            current = m.group(1) if m else None
            comps.setdefault(current, [])
            continue
        m = _INSTR.match(line)
        if not m or current is None:
            continue
        name, rest = m.group(1), m.group(2)
        opcode, at = _opcode_at(" " + rest)
        operands, attrs = _operands(" " + rest, at) if at else ([], "")
        calls = _CALLS.findall(attrs)
        for group in _CALLS_LIST.findall(attrs):
            calls += [c.strip().lstrip("%") for c in group.split(",")]
        meta = _OP_NAME.search(attrs)
        instrs[name] = {"opcode": opcode, "type": rest[:max(at - 1, 0)],
                        "operands": operands, "attrs": attrs,
                        "calls": calls,
                        "op_name": meta.group(1) if meta else ""}
        comps[current].append(name)
    return {"instrs": instrs, "comps": comps}


def _dims(type_str: str) -> list:
    m = _SHAPE.search(type_str)
    if not m:
        raise ValueError(f"no array shape in {type_str!r}")
    return [int(d) for d in m.group(1).split(",") if d]


def dot_flops(ins: dict, instrs: dict) -> float:
    """2 x result elements x the lhs's contracting sizes."""
    out = math.prod(_dims(ins["type"]))
    lhs = _dims(instrs[ins["operands"][0]]["type"])
    m = _CONTRACT.search(ins["attrs"])
    k = math.prod(lhs[int(i)] for i in m.group(1).split(",") if i) \
        if m else 1
    return 2.0 * out * k


def _window(attrs: str, n: int) -> dict:
    """Per spatial dimension: stride, low padding, lhs and rhs
    dilation (XLA's defaults where the window leaves them out)."""
    w = {"stride": [1] * n, "pad": [0] * n, "lhs_dilate": [1] * n,
         "rhs_dilate": [1] * n}
    m = _WINDOW.search(attrs)
    for field in (m.group(1).split() if m else ()):
        key, _, val = field.partition("=")
        if key in w:
            parts = val.split("x")
            w[key] = [int(p.split("_")[0]) for p in parts]
    return w


def conv_flops(ins: dict, instrs: dict) -> float:
    """2 x output batch x output features x input features per group x
    the (output position, kernel tap) pairs that land on the input rather
    than on padding or a dilation hole, per spatial dimension.  XLA on a
    TPU writes matmuls, batched ones too (heads as a dilated spatial
    dimension), as convolutions."""
    m = _LABELS.search(ins["attrs"])
    if not m:
        raise ValueError(f"convolution without dim_labels: {ins['attrs']}")
    ll, rl, ol = m.groups()
    out = _dims(ins["type"])
    lhs = _dims(instrs[ins["operands"][0]]["type"])
    rhs = _dims(instrs[ins["operands"][1]]["type"])
    spatial = sorted(c for c in ol if c.isdigit())
    w = _window(ins["attrs"], len(spatial))
    taps = 1
    for j, c in enumerate(spatial):
        o = np.arange(out[ol.index(c)])[:, None]
        k = np.arange(rhs[rl.index(c)])[None, :]
        pos = o * w["stride"][j] + k * w["rhs_dilate"][j] - w["pad"][j]
        ld = w["lhs_dilate"][j]
        hit = (pos >= 0) & (pos % ld == 0) & (pos // ld < lhs[ll.index(c)])
        taps *= int(hit.sum())
    return 2.0 * out[ol.index("b")] * out[ol.index("f")] \
        * rhs[rl.index("i")] * taps


@dataclasses.dataclass
class Hlo:
    """What the trace reduction reads from the compiled step's HLO, by
    instruction name: its kind ("matmul", "collective", "container",
    "other"), the FLOPs of one execution (the dots and convolutions it
    holds), the JAX op path XLA kept, and each asynchronous ``-start``'s
    ``-done``."""
    kinds: dict
    flops: dict
    labels: dict
    pairs: dict


def read_hlo(text: str) -> Hlo:
    parsed = parse_hlo(text)
    instrs, comps = parsed["instrs"], parsed["comps"]
    memo: dict[str, tuple] = {}

    def own(ins: dict) -> tuple[set, float]:
        op = ins["opcode"]
        if op == "dot":
            return {"matmul"}, dot_flops(ins, instrs)
        if op == "convolution":
            return {"matmul"}, conv_flops(ins, instrs)
        if any(op.startswith(c) for c in COLLECTIVES):
            return {"collective"}, 0.0
        return set(), 0.0

    def whole(name: str) -> tuple[set, float]:
        """Kinds and FLOPs of an instruction with what it wraps."""
        ins = instrs[name]
        kinds, flops = own(ins)
        if ins["opcode"] in WRAPPERS:
            for c in ins["calls"]:
                k, f = holds(c)
                kinds, flops = kinds | k, flops + f
        return kinds, flops

    def holds(comp: str) -> tuple[set, float]:
        if comp not in memo:
            memo[comp] = (set(), 0.0)
            found, flops = set(), 0.0
            for n in comps.get(comp, ()):
                k, f = whole(n)
                found, flops = found | k, flops + f
            memo[comp] = (found, flops)
        return memo[comp]

    kinds, flops = {}, {}
    for name, ins in instrs.items():
        k, f = whole(name)
        if ins["opcode"] in CONTAINERS:
            kinds[name] = "container"
        elif "collective" in k:
            kinds[name] = "collective"
        elif "matmul" in k:
            kinds[name] = "matmul"
            flops[name] = f
        else:
            kinds[name] = "other"
    pairs = {i["operands"][0]: n for n, i in instrs.items()
             if i["opcode"].endswith("-done") and i["operands"]
             and instrs.get(i["operands"][0], {}).get("opcode", "")
             .endswith("-start")}
    return Hlo(kinds, flops,
               {n: i["op_name"] for n, i in instrs.items()}, pairs)


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merge (start, end) pairs into disjoint sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of disjoint sorted ``a`` not covered by disjoint sorted
    ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo: float, hi: float) -> list:
    return subtract([(lo, hi)], union(intervals))


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceTrace:
    name: str
    modules: list      # (start_ns, end_ns)
    ops: list          # (instruction name, start_ns, end_ns)


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float
    end: float


def _device_index(plane_name: str) -> int:
    return int(plane_name.rsplit(":", 1)[1])


def load(path: str):
    """(devices sorted by index, host spans) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}

            def events(line):
                if line not in lines:
                    return []
                return [(op_name(e.name), float(e.start_ns),
                         float(e.end_ns)) for e in lines[line].events]

            devices.append(DeviceTrace(
                plane.name,
                [(s, e) for _, s, e in events("XLA Modules")],
                events("XLA Ops")))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append(HostSpan(e.name, float(e.start_ns),
                                         float(e.end_ns)))
    devices.sort(key=lambda d: _device_index(d.name))
    return devices, host


def in_flight(ops, hlo: Hlo) -> list:
    """(start, end) of each execution of an asynchronous collective:
    the k-th run of a ``-start`` op to the end of the k-th run of the
    ``-done`` that waits on it."""
    runs: dict[str, list] = {}
    for name, s, e in ops:
        runs.setdefault(name, []).append((s, e))
    out = []
    for start, starts in runs.items():
        if start not in hlo.pairs or hlo.kinds[start] != "collective":
            continue
        dones = sorted(runs.get(hlo.pairs[start], []))
        for (s, _), (_, e) in zip(sorted(starts), dones):
            out.append((s, e))
    return out


def reduce_device(dev: DeviceTrace, hlo: Hlo) -> dict:
    """Seconds of one device: the traced window (first program start to
    last program end), busy (union of leaf ops and of asynchronous
    collectives from start to done), matmul (summed durations of matmul
    ops) and the FLOPs those executions did, collective (union of
    collective intervals, asynchronous ones from start to done) and
    exposed collective (collective time in which no compute op runs).
    Every op in the trace has to be in the HLO."""
    if not dev.modules:
        raise ValueError(f"{dev.name}: no program ran in the trace")
    lo = min(s for s, _ in dev.modules)
    hi = max(e for _, e in dev.modules)
    leaf, compute, coll = [], [], []
    matmul = matmul_flops = 0.0
    per_op: dict[str, float] = {}
    for name, s, e in dev.ops:
        k = hlo.kinds[name]
        if k == "container":
            continue
        leaf.append((s, e))
        per_op[name] = per_op.get(name, 0.0) + (e - s)
        if k == "collective":
            coll.append((s, e))
        else:
            compute.append((s, e))
            if k == "matmul":
                matmul += e - s
                matmul_flops += hlo.flops[name]
    coll += in_flight(dev.ops, hlo)
    busy = union(leaf + coll)
    coll_u = union(coll)
    exposed = subtract(coll_u, union(compute))
    return {"window_s": (hi - lo) * 1e-9, "busy_s": length(busy) * 1e-9,
            "matmul_s": matmul * 1e-9, "matmul_flops": matmul_flops,
            "collective_s": length(coll_u) * 1e-9,
            "exposed_s": length(exposed) * 1e-9,
            "per_op_s": {n: v * 1e-9 for n, v in per_op.items()},
            "idle": gaps(busy, lo, hi)}


def label_gaps(idle, host, labels) -> list:
    """[(host label, seconds)] of each idle gap, by the innermost of the
    harness's own host spans (``labels``) that covers its middle."""
    spans = sorted((h for h in host if h.name in labels),
                   key=lambda h: h.end - h.start)
    out = []
    for s, e in idle:
        mid = (s + e) / 2
        name = next((h.name for h in spans if h.start <= mid <= h.end),
                    "none")
        out.append((name, (e - s) * 1e-9))
    return out


def reduce_trace(path: str, hlo: Hlo, labels=(), top: int = 10) -> dict:
    """Per-device readings averaged over the devices, plus the
    breakdown: the device ops that took most time and the longest idle
    gaps (device 0) with what the host was doing."""
    devices, host = load(path)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    per = [reduce_device(d, hlo) for d in devices]
    n = len(per)
    avg = {k: sum(p[k] for p in per) / n
           for k in ("window_s", "busy_s", "matmul_s", "matmul_flops",
                     "collective_s", "exposed_s")}
    ops: dict[str, float] = {}
    for p in per:
        for name, v in p["per_op_s"].items():
            ops[name] = ops.get(name, 0.0) + v / n

    def label(name):
        tail = hlo.labels.get(name, "")
        return f"{name} [{hlo.kinds[name]}] {tail}".strip()[:160]

    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(label_gaps(per[0]["idle"], host, set(labels)),
                  key=lambda kv: -kv[1])[:top]
    return {**avg, "devices": n, "per_device": per,
            "breakdown": {"device_ops": [[label(k), v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in idle]}}
