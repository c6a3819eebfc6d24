"""Static verification of ReduceSchedules and their compiled artifacts.

The paper's lineage (MVAPICH2 tuning tables, Shi et al.'s optimal
trees) treats a collective schedule as something checkable against an
analytic model *before* it runs.  PR 5 made our schedule a first-class
IR (core/schedule.py); this package makes it model-checkable at any
scale — including 512-device production meshes no host run can
execute — with three layers
(DESIGN.md §3.9):

``verify``       rule engine over :class:`repro.core.schedule
                 .ReduceSchedule` objects: byte conservation against
                 the reducers' closed forms, stage pairing/coverage,
                 leaf partition, readiness monotonicity, crossover
                 straddles, wire-dtype tolerance, fingerprint
                 latency-insensitivity (rules ``SV0xx``).
``hlo_lint``     multi-rule pass over compiled HLO text — the
                 generalization of ``roofline.wire_check`` (rules
                 ``HL0xx``, with a warning baseline + suppressions).
``compat_lint``  AST lint banning direct ``jax.experimental.shard_map``
                 / ``maps`` / ``pjit`` & friends outside
                 ``core/compat.py`` (rules ``CL0xx``).

CLI: ``python -m repro.analysis [--source] [--schedules]
[--check-baseline] [--schedule-json FILE]`` — CI gates on zero errors.

Every finding is a :class:`Diagnostic`: a ``rule_id``, a severity
(``error`` gates CI; ``warn`` is baseline-suppressible), and a location
(``bucket[i].stage[j]`` paths from the IR, ``file:line`` from source).
"""
from __future__ import annotations

import dataclasses

ERROR = "error"
WARN = "warn"
SEVERITIES = (ERROR, WARN)


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One finding of one rule at one location."""
    rule_id: str       # "SV001", "HL002", "CL001", ...
    severity: str      # ERROR | WARN
    location: str      # "bucket[3].stage[1]", "src/x.py:17", "" = global
    message: str
    context: str = ""  # what was being checked (cell label, file, ...)

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"severity {self.severity!r} not in "
                             f"{SEVERITIES}")

    def to_json(self) -> dict:
        return {"rule_id": self.rule_id, "severity": self.severity,
                "location": self.location, "message": self.message,
                "context": self.context}

    def render(self) -> str:
        where = ":".join(p for p in (self.context, self.location) if p)
        return f"{self.severity} {self.rule_id} [{where}] {self.message}"


def errors(diags) -> list[Diagnostic]:
    return [d for d in diags if d.severity == ERROR]


def warnings(diags) -> list[Diagnostic]:
    return [d for d in diags if d.severity == WARN]


def summarize(diags, extra: dict | None = None) -> dict:
    """The JSON summary dryrun records and the CLI emits."""
    out = {
        "schema": "repro/analysis/v1",
        "n_errors": len(errors(diags)),
        "n_warnings": len(warnings(diags)),
        "diagnostics": [d.to_json() for d in diags],
    }
    if extra:
        out.update(extra)
    return out


from . import compat_lint, hlo_lint, verify  # noqa: E402  (re-exports)

__all__ = ["Diagnostic", "ERROR", "WARN", "SEVERITIES", "errors",
           "warnings", "summarize", "verify", "hlo_lint", "compat_lint"]
