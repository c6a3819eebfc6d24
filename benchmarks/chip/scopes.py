"""Device time by the program's named scopes: each op of the traced
train step is attributed by the ``op_name`` path that the compiled HLO
keeps for it (``trace_reduce.Hlo.labels``).  A fusion carries its root's
path.

Phases, by the first rule that holds for an op's path:

  aggregation  under the scope ``aggregate`` (the aggregator's packing,
               casts, codec and adds; its collectives are ``agg_ms``'s,
               so only the other ops count here)
  optimizer    under ``clip`` or ``optimizer``
  recompute    ``rematted_computation`` (remat's forward, done again
               inside the backward)
  backward     ``transpose(jvp(``
  forward      ``jvp(``
  unscoped     anything else (parameter copies, the loss's means)

Collectives count to no phase.  Apart from the phases, an op whose path
holds the scope ``sdpa`` counts to the attention core in any phase, and
each model scope (``embed``, ``attention``, ``mlp``, ``norm``, ``head``)
keeps its seconds.  Names are matched whole, with JAX's transformation
wrappers (``jvp(...)``, ``transpose(...)``, ``vmap(...)``) taken off, so a
scope that keeps its name keeps its numbers; a jitted function's name
(``jit(clip)``, jnp's clip) is not a scope.

The readers in ``metrics/`` get the record from :func:`of_run`, which
reads the compiled step's HLO again for the cell (the trace names ops,
the HLO says what each is) and keeps the result in the run's trace
record under ``"scopes"``.
"""
from __future__ import annotations

import re

import trace_reduce as tr

PHASES = ("forward", "backward", "recompute", "optimizer", "aggregation",
          "unscoped")
AGGREGATE = "aggregate"
OPTIMIZER = ("clip", "optimizer")
ATTN_CORE = "sdpa"
MODEL_SCOPES = ("embed", "attention", "mlp", "norm", "head")
# Every scope name the reduction reads.
SCOPES = (AGGREGATE,) + OPTIMIZER + (ATTN_CORE,) + MODEL_SCOPES

# Kept here rather than taken from the program, so that the reduction
# reads a program of any version, one without these scopes too.
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def names(path: str) -> set:
    """The components of an ``op_name`` path, each with its
    transformation wrappers taken off:
    ``jit(step)/transpose(jvp(sdpa))/cos`` -> {jit(step), sdpa, cos}."""
    out = set()
    for part in path.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.add(part)
    return out


def phase(path: str) -> str:
    """The phase of an op by its path (the module's rules, in order)."""
    found = names(path)
    if AGGREGATE in found:
        return "aggregation"
    if found & set(OPTIMIZER):
        return "optimizer"
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(jvp(" in path:
        return "backward"
    if "jvp(" in path:
        return "forward"
    return "unscoped"


def present(hlo: tr.Hlo) -> list:
    """The scopes of ``SCOPES`` that some instruction of the program
    carries: a program built without them has none."""
    seen = set()
    for path in hlo.labels.values():
        seen |= names(path)
    return sorted(seen & set(SCOPES))


def reduce(trace: dict, hlo: tr.Hlo) -> dict:
    """Seconds per phase, of the attention core and per model scope,
    each summed over a device's ops (``per_op_s`` of each entry of
    ``trace["per_device"]``) and averaged over the devices.
    ``compute_s`` is the non-collective ops' summed time, which the
    phases partition."""
    per = trace["per_device"]
    n = len(per)
    phases = dict.fromkeys(PHASES, 0.0)
    model = dict.fromkeys(MODEL_SCOPES, 0.0)
    attn = 0.0
    for dev in per:
        for name, sec in dev["per_op_s"].items():
            if hlo.kinds[name] == "collective":
                continue
            path = hlo.labels.get(name, "")
            phases[phase(path)] += sec / n
            found = names(path)
            if ATTN_CORE in found:
                attn += sec / n
            for s in MODEL_SCOPES:
                if s in found:
                    model[s] += sec / n
    return {"compute_s": sum(phases.values()), "phases": phases,
            "attn_core_s": attn, "model_s": model,
            "present": present(hlo)}


def step_hlo(cell) -> str:
    """The compiled HLO text of the cell's train step, as the harness
    builds it, lowered for the shapes and shardings of its state and
    batch (no device memory is taken)."""
    import jax

    import bench

    prog = bench.build_program(cell, jax.devices())
    mk = bench.makers(prog, cell)
    words = bench.seed_words(0)
    params, opt = jax.eval_shape(mk.state, words)
    pool = jax.eval_shape(mk.pool, words)
    return prog.hlo_text(params, opt, pool[0])


def of_run(run: dict):
    """The scope record of a traced run, made once and kept in its
    trace record; None where the run has no trace."""
    t = run["record"].get("trace")
    if not t:
        return None
    if "scopes" not in t:
        t["scopes"] = reduce(t, tr.read_hlo(step_hlo(run["cell"])))
    return t["scopes"]


def ms_per_step(run: dict, seconds) -> float | None:
    """``seconds(record)`` in ms per traced step, None where the run has
    no trace or ``seconds`` finds nothing to read."""
    s = of_run(run)
    value = None if s is None else seconds(s)
    if value is None:
        return None
    return 1e3 * value / run["record"]["steps"]
