"""Collective linter over compiled HLO text — rules ``HL0xx``.

The generalization of ``launch/roofline.wire_check`` (one hand-rolled
byte comparison) into a multi-rule pass driven by the same
ReduceSchedule IR.  :func:`wire_check` here IS the old function, moved
verbatim — ``roofline.wire_check`` is now a thin wrapper over it, so
every dryrun/report/sweep record is byte-identical — and HL001 turns
its verdict into typed diagnostics alongside three new rules:

``HL001``  per-kind charged collective bytes must cover the IR's
           per-stage ``hlo_bytes`` prediction (the wire check).
``HL002``  ``placement="in_backward"`` must actually interleave: at
           least one full bucket's collective-permutes issue before
           the last backward dot (tests/test_overlap_hlo.py's
           ``perm_vs_dots`` discipline as a lint rule).
``HL003``  no mixed-dtype reduction ops: every all-reduce /
           reduce-scatter must carry one element dtype across its
           operands and results (a silent upcast on the wire
           invalidates the wire-dtype byte accounting).
``HL004``  *warn*: charged all-reduce bytes where the schedule
           predicts a pure RSA/permute decomposition (no ``psum``
           stage) — XLA substituted or added a vendor allreduce.
           Legitimate sources exist (model-axis GSPMD collectives),
           hence warn severity + the baseline.
``HL005``  fused-hop codec soundness: a schedule whose codec'd stages
           run the fused Pallas hop kernel must keep its f32-typed
           collective-permute traffic within the budget of the
           legitimately-f32 payloads (uncoded permute stages) plus one
           4-byte scale scalar per fused coded hop.  An f32 permute
           carrying a full coded payload means XLA's convert-mover
           floated the decode outside the permute — the wire went back
           to 4 bytes/element and the codec's bandwidth win silently
           vanished (the bitcast pinning of ``core/codec.py`` exists
           to prevent exactly this).

Warning baseline: ``ANALYSIS_BASELINE.json`` (schema
``repro/analysis-baseline/v1``) at the repo root lists accepted
warnings as ``{"rule_id": ..., "context": ...}`` entries (``"*"``
context matches everywhere).  ``--check-baseline`` fails the CLI on
any warning NOT in the baseline — errors are never baselinable.
Inline suppression: a line ``analysis-suppress: HL003[, HL004]``
anywhere in the linted text disables those rules for that text.
"""
from __future__ import annotations

import json
import os
import re

from repro.core import reducers

from . import ERROR, WARN, Diagnostic

RULES = {
    "HL001": "charged collective bytes cover the IR per-stage bytes",
    "HL002": "in_backward schedules interleave >=1 bucket before the "
             "last backward dot",
    "HL003": "no mixed-dtype reduction ops",
    "HL004": "no unexpected all-reduce under an RSA decomposition "
             "(warn)",
    "HL005": "fused codec'd schedules keep f32 permute traffic within "
             "the scale-scalar budget (no free-floating converts)",
}

BASELINE_SCHEMA = "repro/analysis-baseline/v1"
BASELINE_FILE = "ANALYSIS_BASELINE.json"

_SUPPRESS_RE = re.compile(r"analysis-suppress:\s*([A-Z0-9, ]+)")
_REDUCTION_RE = re.compile(r"\b(all-reduce|reduce-scatter)(?:-start)?\(")
_DTYPE_RE = re.compile(
    r"\b(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|"
    r"f8e4m3fn|f8e5m2|s4|u4)\[")


# ---------------------------------------------------------------------------
# wire_check — moved verbatim from launch/roofline.py (which now wraps
# this; the dict it returns is pinned by tests/test_claims.py)
# ---------------------------------------------------------------------------

def wire_check(sched, collective_bytes, rel_tol: float = 0.02) -> dict:
    """Measured-vs-modeled comm-byte consistency (DESIGN.md §3.7/§4):
    compare the HLO-charged collective bytes of a compiled step against
    the per-STAGE wire bytes carried by the resolved
    :class:`repro.core.schedule.ReduceSchedule` — no independent
    re-derivation: the IR the aggregator executed is the same object
    being verified.

    ``sched``: a ReduceSchedule (attached or detached/deserialized).
    ``collective_bytes``: the per-kind byte dict from the HLO parse.
    Each stage predicts the HLO kind it compiles to (``Stage.hlo_kind``:
    ppermute schedules → collective-permute, ``psum`` → all-reduce
    payload, ``ps_gather`` → all-gather) and the bytes it charges
    (``Stage.hlo_bytes``).  The charged side may legitimately exceed
    the prediction (model-axis GSPMD collectives, padding on
    non-divisible chunks), so the
    verdict is per kind: ``consistent`` = every predicted kind is
    within ``rel_tol`` below the charge it explains or lower.
    """
    predicted: dict = {}
    for bucket in sched.buckets:
        for st in bucket.stages:
            if st.hlo_kind is None:
                continue             # "shard" bracket opener: local
            predicted[st.hlo_kind] = predicted.get(st.hlo_kind, 0) \
                + st.hlo_bytes
    charged = {k: int(v) for k, v in collective_bytes.items()}
    kinds = {}
    for kind, want in sorted(predicted.items()):
        got = charged.get(kind, 0)
        kinds[kind] = {
            "predicted": int(want), "charged": got,
            "ratio": (got / want) if want else None,
            # charged >= predicted*(1-tol): the schedule's bytes are in
            # the HLO (extra charge from other collectives is allowed)
            "ok": got >= want * (1.0 - rel_tol),
        }
    return {
        "axis_sizes": list(sched.axis_sizes),
        "predicted_total": int(sum(predicted.values())),
        "charged_total": int(sum(charged.values())),
        "kinds": kinds,
        "consistent": all(k["ok"] for k in kinds.values()),
    }


# ---------------------------------------------------------------------------
# per-stage permute accounting (HL002)
# ---------------------------------------------------------------------------

def stage_permute_steps(stage) -> int:
    """collective-permute ops one stage compiles to (0 for stages that
    lower to vendor all-reduce / all-gather)."""
    if stage.hlo_kind != "collective-permute":
        return 0
    if stage.op == "allreduce":
        return reducers.allreduce_steps(stage.algorithm, stage.axis_size)
    # one ring pass: reduce_scatter and all_gather each take p-1 hops
    return max(stage.axis_size - 1, 0)


def min_bucket_permute_steps(sched) -> int:
    """Permute count of the cheapest full bucket — the least HL002 can
    demand before the last backward dot (0 when no bucket permutes)."""
    counts = [sum(stage_permute_steps(st) for st in b.stages)
              for b in sched.buckets]
    counts = [c for c in counts if c > 0]
    return min(counts) if counts else 0


_F32_SHAPE = re.compile(r"\bf32\[([\d,]*)\]")


def f32_permute_bytes(hlo_text: str) -> int:
    """f32 payload bytes moved by collective-permute instructions — the
    measured side of HL005.  Per permute line the LARGEST single f32
    shape token counts (a ``-start``'s tuple type lists the aliased
    input and output once each; the payload must not be double-charged),
    summed over every permute in the text."""
    total = 0
    for line in hlo_text.splitlines():
        # Split at the OP token (with its paren) — the instruction's
        # own %collective-permute.N name appears first on the line and
        # must not truncate the head before the result type.
        for marker in ("collective-permute-start(",
                       "collective-permute("):
            if marker in line:
                head = line.split(marker, 1)[0]
                break
        else:
            continue
        best = 0
        for m in _F32_SHAPE.finditer(head):
            n = 1
            for d in m.group(1).split(","):
                if d:
                    n *= int(d)
            best = max(best, n * 4)
        total += best
    return total


def fused_f32_permute_budget(sched) -> int:
    """Upper bound on LEGITIMATE f32 permute bytes of a fused codec'd
    schedule: uncoded (or unfused) permute stages move their full
    payload in f32, and each fused coded hop carries exactly one
    4-byte f32 absmax scalar next to its bit-pinned payload."""
    budget = 0
    for b in sched.buckets:
        for st in b.stages:
            if st.hlo_kind != "collective-permute":
                continue
            coded = (getattr(st, "codec", "none") or "none") != "none"
            if coded and getattr(st, "fused_hop", False):
                budget += stage_permute_steps(st) * 4
            else:
                budget += st.hlo_bytes
    return budget


def perm_vs_dots(hlo_text: str) -> tuple[int, int]:
    """(permutes before the last dot, total permutes) — the overlap
    witness of tests/test_overlap_hlo.py."""
    lines = hlo_text.splitlines()
    perms = [i for i, l in enumerate(lines) if "collective-permute(" in l]
    dots = [i for i, l in enumerate(lines) if " dot(" in l]
    if not dots:
        return 0, len(perms)
    return sum(1 for i in perms if i < dots[-1]), len(perms)


# ---------------------------------------------------------------------------
# the lint pass
# ---------------------------------------------------------------------------

def _suppressed(hlo_text: str) -> set[str]:
    out: set[str] = set()
    for m in _SUPPRESS_RE.finditer(hlo_text):
        out.update(t.strip() for t in m.group(1).split(",") if t.strip())
    return out


def lint_hlo(sched, hlo_text: str | None = None,
             collective_bytes=None, rel_tol: float = 0.02,
             context: str = "") -> list[Diagnostic]:
    """Run every HL rule.  ``hlo_text`` drives HL002/HL003 (and, via
    the loop-corrected parser, HL001/HL004 when ``collective_bytes``
    is not given); a pre-parsed per-kind byte dict may be passed
    instead when only the byte rules are wanted."""
    out: list[Diagnostic] = []
    skip = _suppressed(hlo_text) if hlo_text else set()
    if collective_bytes is None and hlo_text is not None:
        from repro.launch import hlo_analysis
        collective_bytes = hlo_analysis.analyze(hlo_text).collective_bytes

    if collective_bytes is not None and "HL001" not in skip:
        wc = wire_check(sched, collective_bytes, rel_tol=rel_tol)
        for kind, k in wc["kinds"].items():
            if not k["ok"]:
                out.append(Diagnostic(
                    "HL001", ERROR, kind,
                    f"HLO charges {k['charged']}B of {kind} but the "
                    f"schedule's stages predict {k['predicted']}B "
                    f"(ratio {k['ratio']:.3f} < 1-{rel_tol})",
                    context=context))

    if hlo_text is not None and "HL002" not in skip \
            and sched.placement == "in_backward":
        need = min_bucket_permute_steps(sched)
        before, total = perm_vs_dots(hlo_text)
        if need > 0 and before < need:
            out.append(Diagnostic(
                "HL002", ERROR, "",
                f"placement='in_backward' but only {before} of {total} "
                f"collective-permutes issue before the last backward "
                f"dot (a full bucket needs {need}): the reductions "
                f"serialized into a trailing block", context=context))

    if hlo_text is not None and "HL003" not in skip:
        for ln, line in enumerate(hlo_text.splitlines(), 1):
            if not _REDUCTION_RE.search(line):
                continue
            dtypes = set(_DTYPE_RE.findall(line.split("metadata=")[0]))
            if len(dtypes) > 1:
                out.append(Diagnostic(
                    "HL003", ERROR, f"hlo:{ln}",
                    f"mixed-dtype reduction op ({'/'.join(sorted(dtypes))})"
                    f": wire-dtype byte accounting no longer holds",
                    context=context))

    if hlo_text is not None and "HL005" not in skip:
        fused_coded = any(
            getattr(st, "fused_hop", False)
            and (getattr(st, "codec", "none") or "none") != "none"
            for b in sched.buckets for st in b.stages)
        if fused_coded:
            got = f32_permute_bytes(hlo_text)
            budget = fused_f32_permute_budget(sched)
            # floor absorbs GSPMD bookkeeping permutes outside the
            # schedule (same spirit as HL004's vendor-collective floor)
            allowed = budget + max(1024, budget // 100)
            if got > allowed:
                out.append(Diagnostic(
                    "HL005", ERROR, "collective-permute",
                    f"fused codec'd schedule moves {got}B of f32 "
                    f"collective-permute payload but only {budget}B are "
                    f"legitimate (uncoded payloads + one 4B scale per "
                    f"fused hop): a convert floated outside a permute "
                    f"and the coded wire decayed to f32",
                    context=context))

    if collective_bytes is not None and "HL004" not in skip:
        expects_ar = any(st.hlo_kind == "all-reduce"
                         for b in sched.buckets for st in b.stages)
        charged_ar = int(collective_bytes.get("all-reduce", 0))
        predicted_total = sum(st.hlo_bytes for b in sched.buckets
                              for st in b.stages)
        floor = max(1024, predicted_total // 100)
        if not expects_ar and charged_ar > floor and sched.buckets:
            out.append(Diagnostic(
                "HL004", WARN, "all-reduce",
                f"schedule decomposes into RSA/permute stages only, "
                f"but the HLO charges {charged_ar}B of vendor "
                f"all-reduce (> {floor}B): XLA substituted or added a "
                f"collective outside the schedule", context=context))
    return out


# ---------------------------------------------------------------------------
# warning baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str | None = None) -> list[dict]:
    """Accepted-warning entries from ``ANALYSIS_BASELINE.json`` (repo
    root by default); [] when the file does not exist."""
    if path is None:
        path = BASELINE_FILE
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rec = json.load(f)
    if rec.get("schema") != BASELINE_SCHEMA:
        raise ValueError(f"baseline schema must be {BASELINE_SCHEMA!r}, "
                         f"got {rec.get('schema')!r}")
    return list(rec.get("warnings", []))


def baselined(diag: Diagnostic, baseline: list[dict]) -> bool:
    """Does an accepted-warning entry cover this diagnostic?  Errors
    are never baselinable."""
    if diag.severity != WARN:
        return False
    for entry in baseline:
        if entry.get("rule_id") != diag.rule_id:
            continue
        ctx = entry.get("context", "*")
        if ctx in ("*", diag.context):
            return True
    return False


def unbaselined_warnings(diags, baseline: list[dict]) -> list[Diagnostic]:
    return [d for d in diags
            if d.severity == WARN and not baselined(d, baseline)]
