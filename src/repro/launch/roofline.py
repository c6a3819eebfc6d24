"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds (DESIGN.md §4):

    compute    = HLO_FLOPs  / (chips × peak_bf16)
    memory     = HLO_bytes  / (chips × HBM_bw)
    collective = Σ per-axis collective_bytes / (chips × link_bw(axis))

``cost_analysis`` provides flops/bytes. Collective bytes are NOT in
cost_analysis: we parse the compiled HLO text and sum operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops, classifying each by the mesh axis its replica_groups span (cross-pod
groups get DCN bandwidth).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

from repro.core import hw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:%?[\w.\-]+\s*=\s*)?"
    r"(?:\(?[\w\[\],{}\/ ]*\)?\s*)"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.MULTILINE)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    bytes_by_kind: dict
    total_bytes: int

    def to_dict(self):
        return {"counts": self.counts, "bytes_by_kind": self.bytes_by_kind,
                "total_bytes": self.total_bytes}


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over all tensor shapes in an HLO result-type string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand/result sizes of collective ops in (compiled) HLO text.

    Works on post-optimization HLO: every collective line looks like
      %x = bf16[128,1024]{...} all-reduce(...), replica_groups=...
    We charge the RESULT size (per-participant payload) per op, the
    standard convention for wire-byte accounting of allreduce-family ops.
    """
    counts: dict = {}
    bytes_by: dict = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        kind = m.group(1)
        # result type(s) appear before the op name on the lhs
        lhs = line.split("=", 1)
        shape_src = lhs[1].split(m.group(0))[0] if len(lhs) == 2 else line
        nbytes = _shape_bytes(shape_src)
        counts[kind] = counts.get(kind, 0) + 1
        bytes_by[kind] = bytes_by.get(kind, 0) + nbytes
    return CollectiveStats(counts, bytes_by, sum(bytes_by.values()))


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float

    def to_dict(self):
        return dataclasses.asdict(self)


def compute_roofline_from_aggregate(agg, chips: int, model_flops: float,
                                    chip: hw.Chip = hw.V5E) -> Roofline:
    """agg: hlo_analysis.Aggregate (loop-corrected, per-device)."""
    compute_s = agg.flops / chip.peak_bf16_flops
    memory_s = agg.hbm_bytes / chip.hbm_bandwidth
    collective_s = agg.total_collective_bytes / chip.ici_link_bandwidth
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_flops = agg.flops * chips
    return Roofline(
        flops=agg.flops, hbm_bytes=agg.hbm_bytes,
        collective_bytes=agg.total_collective_bytes,
        chips=chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops,
        useful_ratio=(model_flops / total_flops) if total_flops else 0.0)


def step_estimate_s(roof: "Roofline",
                    exposed_collective_s: float | None = None) -> float:
    """Single-number step prediction from the roofline terms: the
    dominant on-chip term plus the collective term.  With
    ``exposed_collective_s`` (from an overlap Timeline) only the
    communication the backward could NOT hide is charged; ``None``
    charges the fully serialized collective term (the no-overlap
    baseline)."""
    coll = roof.collective_s if exposed_collective_s is None \
        else exposed_collective_s
    return max(roof.compute_s, roof.memory_s) + coll


def wire_check(sched, collective_bytes, rel_tol: float = 0.02) -> dict:
    """Measured-vs-modeled comm-byte consistency (DESIGN.md §3.7/§4) —
    now rule HL001 of the collective linter.  The implementation lives
    in :mod:`repro.analysis.hlo_lint` (same dict, moved verbatim; this
    wrapper keeps every dryrun/report/sweep record byte-identical) so
    the byte comparison composes with the linter's other HLO rules,
    rule IDs, and warning baseline instead of staying a one-off."""
    from repro.analysis import hlo_lint
    return hlo_lint.wire_check(sched, collective_bytes, rel_tol=rel_tol)


def overlap_report(roof: "Roofline", timeline) -> dict:
    """Predicted overlap efficiency of a config: the timeline's hidden/
    exposed split rescaled to the roofline's HLO-charged collective
    term (the timeline's own comm_s is the cost model's estimate; the
    charged bytes are ground truth), plus serialized-vs-overlapped step
    predictions.  Hidden comm is capped at the backward span — when the
    charged term dwarfs the cost-model estimate, rescaling alone would
    claim more hiding than the backward window physically offers."""
    hidden = min(roof.collective_s * timeline.overlap_fraction,
                 timeline.backward_s)
    frac = hidden / roof.collective_s if roof.collective_s > 0 else 1.0
    exposed = roof.collective_s - hidden
    return {
        "overlap_fraction": frac,
        "hidden_comm_s": hidden,
        "exposed_comm_s": exposed,
        "step_serial_s": step_estimate_s(roof),
        "step_overlapped_s": step_estimate_s(roof,
                                             exposed_collective_s=exposed),
        "timeline": timeline.to_dict(),
    }


def compute_roofline(cost: dict, coll: CollectiveStats, chips: int,
                     model_flops: float,
                     chip: hw.Chip = hw.V5E) -> Roofline:
    """cost: compiled.cost_analysis() dict (per-device numbers)."""
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    compute_s = flops / chip.peak_bf16_flops
    memory_s = hbm / chip.hbm_bandwidth
    collective_s = coll.total_bytes / chip.ici_link_bandwidth
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_flops = flops * chips
    return Roofline(
        flops=flops, hbm_bytes=hbm, collective_bytes=coll.total_bytes,
        chips=chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops,
        useful_ratio=(model_flops / total_flops) if total_flops else 0.0)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) per step
# ---------------------------------------------------------------------------

def active_params(spec) -> float:
    """Active parameter count (MoE counts top_k + shared experts only;
    of the experts a chip holds, their share of the top_k)."""
    total = 0.0
    if spec.num_experts:
        # replace the held expert bank with its active share
        per_expert = 3 * spec.d_model * spec.moe_d_ff
        n_moe_layers = spec.num_layers - spec.first_dense_layers
        held = spec.held_experts
        total -= n_moe_layers * held * per_expert
        total += n_moe_layers * (spec.top_k * held / spec.num_experts
                                 + spec.num_shared_experts) * per_expert
    return total


def model_flops(spec, shape, params_total: float) -> float:
    """6·N·D for training, 2·N·D for inference forward/decode."""
    n = params_total + active_params(spec)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
