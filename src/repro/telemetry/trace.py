"""Span tracing: nested host-timed spans around executed work.

A :class:`Span` is ``(name, cat, t0, t1, attrs, children)``, recorded
by a :class:`Tracer` on the host clock.  The only category is
``cat="wall"``: host wall-clock around executed work (the launchers'
dry-run replay, the serve engine's prefill and decode calls, the
closure's ``probe:`` replays).

Every span, while open, also holds a ``jax.profiler.TraceAnnotation``
of its name, so under ``jax.profiler.start_trace`` it appears on the
profiler's host plane on the device trace's clock, and an idle gap on
the device can be attributed to it.  What runs INSIDE a compiled step
is not a span: the program carries ``jax.named_scope`` names (the
model's layers, ``aggregate/bucket[i]/stage[j]/hop[k]``, ``clip``,
``optimizer``) in its HLO metadata, and the device trace times them
(DESIGN.md §3.11).

The exporter writes a single JSON file that is both Perfetto/
``chrome://tracing`` loadable (top-level ``traceEvents`` in the
``trace_event`` format) and schema-versioned (the full span tree under
the ``repro`` key, schema ``repro/trace/v1``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax

TRACE_SCHEMA = "repro/trace/v1"

# Environment opt-in: any non-empty value enables the global tracer at
# import time (the CLI drivers additionally accept explicit flags).
ENV_VAR = "REPRO_TRACE"

CATEGORIES = ("wall",)


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Process-wide telemetry switch.  Off by default."""

    enabled: bool = False

    @staticmethod
    def from_env() -> "TelemetryConfig":
        return TelemetryConfig(enabled=bool(os.environ.get(ENV_VAR)))


@dataclasses.dataclass
class Span:
    name: str
    cat: str = "wall"
    t0: float = 0.0
    t1: float = 0.0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def set(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "cat": self.cat,
            "t0": self.t0,
            "t1": self.t1,
            "attrs": dict(self.attrs),
            "children": [c.to_json() for c in self.children],
        }

    @staticmethod
    def from_json(rec: dict) -> "Span":
        return Span(
            name=rec["name"],
            cat=rec.get("cat", "wall"),
            t0=float(rec["t0"]),
            t1=float(rec["t1"]),
            attrs=dict(rec.get("attrs", {})),
            children=[Span.from_json(c) for c in rec.get("children", [])],
        )


class _NullSpan:
    """Shared no-op context manager returned when tracing is disabled.

    A single module-level instance keeps the disabled fast path
    allocation-free: ``tracer.span(...)`` costs one attribute check and
    returns this object.
    """

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, key: str, value: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    __slots__ = ("_tracer", "span", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span
        self._annotation = jax.profiler.TraceAnnotation(span.name)

    def __enter__(self) -> Span:
        self._annotation.__enter__()
        self._tracer._push(self.span)
        return self.span

    def __exit__(self, *exc) -> None:
        self._tracer._pop(self.span)
        self._annotation.__exit__(*exc)


class Tracer:
    """Collects a forest of nested spans.

    Not thread-safe by design: every instrumented path (launcher wall
    timers, the serve engine, the replay probe) runs on one thread.
    """

    def __init__(self, config: Optional[TelemetryConfig] = None):
        self.config = config if config is not None else TelemetryConfig()
        self.roots: List[Span] = []
        self._stack: List[Span] = []

    @property
    def enabled(self) -> bool:
        return self.config.enabled

    def span(self, name: str, cat: str = "wall", **attrs):
        """Open a nested span; returns a context manager.

        When disabled this returns the shared no-op context manager
        without recording anything.
        """
        if not self.config.enabled:
            return _NULL_SPAN
        if cat not in CATEGORIES:
            raise ValueError(f"unknown span category {cat!r}; "
                             f"expected one of {CATEGORIES}")
        return _SpanCtx(self, Span(name=name, cat=cat, attrs=attrs))

    def _push(self, span: Span) -> None:
        span.t0 = time.perf_counter()
        if self._stack:
            self._stack[-1].children.append(span)
        else:
            self.roots.append(span)
        self._stack.append(span)

    def _pop(self, span: Span) -> None:
        span.t1 = time.perf_counter()
        # Close any dangling descendants too (exception unwinds).
        while self._stack and self._stack[-1] is not span:
            inner = self._stack.pop()
            if not inner.t1:
                inner.t1 = span.t1
        if self._stack and self._stack[-1] is span:
            self._stack.pop()

    def clear(self) -> None:
        self.roots = []
        self._stack = []

    # -- export ---------------------------------------------------------

    def iter_spans(self):
        """All spans, depth-first."""
        stack = list(reversed(self.roots))
        while stack:
            s = stack.pop()
            yield s
            stack.extend(reversed(s.children))

    def to_json(self) -> dict:
        return {
            "schema": TRACE_SCHEMA,
            "spans": [s.to_json() for s in self.roots],
        }

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON object format (Perfetto-loadable).

        Nested spans become stacked ``"ph": "X"`` complete events on one
        track; timestamps are microseconds relative to the earliest
        span.  The full ``repro/trace/v1`` record rides along under the
        ``repro`` key (the trace_event spec allows extra top-level
        metadata keys).
        """
        spans = list(self.iter_spans())
        t_base = min((s.t0 for s in spans), default=0.0)
        events = []
        for s in spans:
            events.append({
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": (s.t0 - t_base) * 1e6,
                "dur": max(s.duration_s, 0.0) * 1e6,
                "pid": 0,
                "tid": 0,
                "args": {k: v for k, v in s.attrs.items()},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "repro": self.to_json(),
        }

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1, sort_keys=True)
            f.write("\n")


def from_json(rec: dict) -> List[Span]:
    """Parse a ``repro/trace/v1`` record back into a span forest."""
    if rec.get("schema") != TRACE_SCHEMA:
        raise ValueError(f"not a {TRACE_SCHEMA} record: "
                         f"schema={rec.get('schema')!r}")
    return [Span.from_json(s) for s in rec.get("spans", [])]


# -- module-global tracer ----------------------------------------------

_GLOBAL = Tracer(TelemetryConfig.from_env())


def get_tracer() -> Tracer:
    return _GLOBAL


def configure(config: TelemetryConfig) -> Tracer:
    """Install a fresh global tracer with ``config``; returns it."""
    global _GLOBAL
    _GLOBAL = Tracer(config)
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL.config.enabled
