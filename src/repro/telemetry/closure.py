"""Measured-vs-predicted timeline closure (DESIGN.md §3.11).

The cost model predicts a latency for every IR stage
(``Stage.predicted_s``) and the §3.6 simulator turns those into an
overlap timeline — but nothing in the repo measured what an executed
stage actually costs.  Per-stage host timing *inside* one compiled step
is impossible (DESIGN.md D1: no hardware timeline on the host-CPU
backend), so the closure uses a **measured replay**: each distinct IR
stage is re-executed as its own jitted ``shard_map`` collective on a
dedicated submesh of ``axis_size`` devices, host-timed around
``block_until_ready`` (warm-up call, then best-of-reps — the same idiom
as the codec sweep in ``benchmarks/allreduce_micro.py``).

Host wall-clock and the TPU-anchored cost model differ by orders of
magnitude, so residuals are gated through a single fitted scalar per
schedule: ``k = Σ(measured·predicted) / Σ(predicted²)`` (least squares
through the origin, over stages large enough to be bandwidth-bound).
The per-stage ratio ``max(m/(k·p), (k·p)/m)`` must sit inside a
declared two-sided band — the codec-sweep discipline (§3.10), with a
wider factor because host timers see scheduler noise the model cannot.
Only stages whose wire bytes fall inside the calibration regime
``[MIN_BAND_BYTES, MAX_BAND_BYTES]`` are fitted and gated: below it
dispatch latency (the host α) dominates, above it the host backend's
cache/NUMA curvature does, and neither has anything to do with the
model's constants.  Out-of-regime stages are reported with their
ratio but do not trip the band.

``BENCH_telemetry.json`` commits one such closure for a canonical p=8
cell set; ``check_artifact`` re-derives the predicted side from the
CURRENT cost model without re-measuring, so a cost-model change that
forgets a re-emit fails the regen currency gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from . import metrics as metrics_mod
from . import trace as trace_mod

TELEMETRY_SCHEMA = "repro/telemetry/v1"

# Two-sided residual band (codec-sweep style, §3.10): measured within
# BAND_FACTOR× of k·predicted, both directions.  Wider than the codec
# band's 3.0 — host wall-clock carries scheduler/allocator noise the
# TPU-anchored model has no term for.
BAND_FACTOR = 5.0

# Stages with fewer wire bytes than this are α-dominated on the host
# (latency floor of a jitted dispatch ≈ tens of µs) and are reported
# but excluded from both the k fit and the band gate.
MIN_BAND_BYTES = 256 * 1024

# ... and stages with MORE wire bytes than this sit above the host
# backend's cache/NUMA knee, where effective bandwidth degrades with
# buffer size (measured/predicted GROWS with bytes — curvature no
# single per-axis-size k can absorb).  The committed artifact cells
# all live inside [MIN, MAX]; stages outside the regime are reported
# with their ratio but neither fitted nor gated.
MAX_BAND_BYTES = 64 * 1024 * 1024

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", "..", ".."))
TELEMETRY_ARTIFACT = os.path.join(_ROOT, "BENCH_telemetry.json")


# ---------------------------------------------------------------------------
# measured replay: one jitted collective per distinct IR stage
# ---------------------------------------------------------------------------

def stage_key(st) -> tuple:
    """Dedup key: stages with the same (op, algorithm, axis size,
    payload, codec) replay identically, whatever bucket they sit in."""
    return (st.op, st.algorithm, int(st.axis_size), int(st.n_bytes),
            getattr(st, "codec", "none") or "none")


def _stage_callable(st):
    """The per-shard body replaying ONE stage standalone.

    ``all_gather`` stages cannot go through ``execute_stages`` alone
    (the executor pairs them with their scatter), so the ring reducers
    are driven directly; the payload semantics match the IR: the local
    buffer carries ``st.n_bytes`` (the stage's input payload on the
    busiest device).
    """
    from repro.core import reducers

    if st.op == "reduce_scatter":
        permute = reducers._stage_permute(st)

        def body(x):
            return reducers.ring_reduce_scatter(
                x, st.axis, permute=permute)[0]
    elif st.op == "all_gather":
        permute = reducers._stage_permute(st)
        p = int(st.axis_size)

        def body(x):
            return reducers.ring_all_gather(
                x, st.axis, x.shape[0] * p, permute=permute)
    else:
        def body(x):
            return reducers.execute_stages(x, [st])
    return body


def measure_stage(st, wire_dtype: str = "float32", reps: int = 3,
                  devices=None) -> float:
    """Best-of-``reps`` host seconds for one stage replayed on a fresh
    single-axis mesh of ``st.axis_size`` devices (after one warm-up
    call that absorbs compilation)."""
    import jax
    import numpy as np

    from repro.core import compat

    p = int(st.axis_size)
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < p:
        raise ValueError(f"stage needs {p} devices on axis "
                         f"{st.axis!r}; only {len(devs)} available")
    mesh = compat.make_mesh((p,), (st.axis,), devices=devs[:p])
    P = jax.sharding.PartitionSpec
    n = max(int(st.n_bytes) // np.dtype(wire_dtype).itemsize, 1)
    x = (np.arange(p * n, dtype=wire_dtype) % 13 - 6.0).astype(wire_dtype)
    fn = jax.jit(compat.shard_map(
        _stage_callable(st), mesh,
        in_specs=P(st.axis), out_specs=P(st.axis), check_vma=False))
    fn(x).block_until_ready()            # warm-up: compile + first run
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_schedule(sched, wire_dtype: str = "", reps: int = 3,
                     devices=None,
                     tracer: Optional[trace_mod.Tracer] = None
                     ) -> Dict[str, float]:
    """Replay every stage of ``sched`` (deduplicated by
    :func:`stage_key`); returns ``{ir_path: measured_s}`` covering ALL
    paths, duplicates sharing one measurement.  When a tracer is given
    (or the global one is enabled) each distinct replay records a wall
    span named by its IR path."""
    wire = wire_dtype or sched.wire_dtype
    tr = tracer if tracer is not None else trace_mod.get_tracer()
    cache: Dict[tuple, float] = {}
    out: Dict[str, float] = {}
    for path, _bucket, st in sched.iter_stages():
        if st.op == "shard":
            # model-bracket opener: a local slice, nothing on the wire —
            # recorded at zero so closure_report keeps full path
            # coverage (wire_bytes=0 keeps it out of the gated band)
            out[path] = 0.0
            continue
        key = stage_key(st)
        if key not in cache:
            with tr.span(f"probe:{path}", cat="wall", ir_path=path,
                         op=st.op, algorithm=st.algorithm,
                         axis_size=int(st.axis_size),
                         n_bytes=int(st.n_bytes),
                         wire_bytes=int(st.wire_bytes),
                         codec=getattr(st, "codec", "none") or "none",
                         reps=reps) as sp:
                cache[key] = measure_stage(st, wire, reps=reps,
                                           devices=devices)
                sp.set("measured_s", cache[key])
            metrics_mod.REGISTRY.histogram(
                "probe_stage_s",
                help="measured-replay stage latency (s)").observe(
                    cache[key], op=st.op, algorithm=st.algorithm)
        out[path] = cache[key]
    return out


# ---------------------------------------------------------------------------
# fused-vs-unfused replay (DESIGN.md §3.13)
# ---------------------------------------------------------------------------

def measure_fused_replay(sched, reps: int = 3, devices=None) -> dict:
    """Replay one schedule through BOTH execution routes and time them.

    Unfused: every ``fused_hop`` flag cleared, each bucket's stage walk
    as its own per-call jitted ``shard_map`` — the pre-§3.13 path.
    Fused: every fusable flag set, executed through a cached
    :class:`~repro.core.plan_cache.StageExecutor`.  BOTH routes donate
    their input buffers and chain ``bufs = run(bufs)`` across reps:
    donation is not free on every algorithm (a ring hop reads the
    whole input at every step, so in-place reuse costs XLA a buffer
    copy), and donating only one side would fold that
    allocation-discipline toll into what should be a pure
    execution-route comparison.

    Returns measured best-of-reps seconds for both routes, the
    speedup, a fused-vs-unfused numeric residual (absmax-relative —
    the SV008/SV009 comparison discipline; bit-exact for
    none/bf16 wires, FMA-contraction 1-ulp territory for int8/fp8),
    and the executor-cache stats after the run."""
    import jax
    import numpy as np

    from repro.core import compat, reducers
    from repro.core import schedule as schedule_mod
    from repro.core.plan_cache import GLOBAL_EXECUTOR_CACHE

    p = 1
    for s in sched.axis_sizes:
        p *= int(s)
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < p:
        raise ValueError(f"schedule needs {p} devices over axes "
                         f"{sched.axis_names}; only {len(devs)} "
                         f"available")
    mesh = compat.make_mesh(tuple(int(s) for s in sched.axis_sizes),
                            tuple(sched.axis_names), devices=devs[:p])
    P = jax.sharding.PartitionSpec
    spec = P(tuple(sched.axis_names))
    sharding = jax.sharding.NamedSharding(mesh, spec)
    itemsize = np.dtype(sched.wire_dtype).itemsize
    host = []
    for b in sched.buckets:
        n = max(int(b.n_bytes) // itemsize, 1)
        host.append(((np.arange(p * n) % 13) - 6.0)
                    .astype(sched.wire_dtype))

    def fresh():
        return [jax.device_put(np.array(h), sharding) for h in host]

    fused = schedule_mod.with_fused_hops(sched, True)
    unfused = schedule_mod.with_fused_hops(sched, False)

    fns = [jax.jit(compat.shard_map(
        lambda xl, _st=b.stages: reducers.execute_stages(xl, _st),
        mesh, in_specs=spec, out_specs=spec,
        axis_names=set(sched.axis_names), check_vma=False),
        donate_argnums=0)
        for b in unfused.buckets]

    def run_unfused(bufs):
        out = [fn(x) for fn, x in zip(fns, bufs)]
        for o in out:
            o.block_until_ready()
        return out

    # reference values for the residual: a NON-donated copy of the walk
    # (run_unfused consumes its inputs)
    ref = [np.array(jax.jit(compat.shard_map(
        lambda xl, _st=b.stages: reducers.execute_stages(xl, _st),
        mesh, in_specs=spec, out_specs=spec,
        axis_names=set(sched.axis_names), check_vma=False))(x))
        for b, x in zip(unfused.buckets, fresh())]
    run_unfused(fresh())                    # warm-up: compile

    ex = GLOBAL_EXECUTOR_CACHE.executor_for(fused, fresh(), mesh)
    got = ex(*fresh())                      # warm-up: trace + compile
    for o in got:
        o.block_until_ready()

    # INTERLEAVED best-of-reps: host-device wall clocks drift with
    # ambient load, so timing one route's whole block before the
    # other's folds that drift into the speedup; alternating reps
    # samples both routes under the same conditions and best-of
    # discards the pauses
    best_u = best_f = float("inf")
    bufs_u, bufs_f = fresh(), fresh()
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        bufs_u = run_unfused(bufs_u)        # donated chain
        best_u = min(best_u, time.perf_counter() - t0)
        t0 = time.perf_counter()
        bufs_f = ex(*bufs_f)                # donated chain
        for o in bufs_f:
            o.block_until_ready()
        best_f = min(best_f, time.perf_counter() - t0)

    max_ratio = 0.0
    for r, g in zip(ref, got):
        absmax = float(np.max(np.abs(np.asarray(r))))
        diff = float(np.max(np.abs(np.asarray(g) - np.asarray(r))))
        if absmax > 0:
            max_ratio = max(max_ratio, diff / absmax)
        elif diff > 0:
            max_ratio = float("inf")
    metrics_mod.record_executor_cache(GLOBAL_EXECUTOR_CACHE)
    return {
        "unfused_s": best_u,
        "fused_s": best_f,
        "speedup": (best_u / best_f) if best_f > 0 else float("inf"),
        "residual_rel": max_ratio,
        "executor_traces": ex.traces,
        "executor_stats": GLOBAL_EXECUTOR_CACHE.stats(),
    }


# ---------------------------------------------------------------------------
# calibration + residual table
# ---------------------------------------------------------------------------

def calibrate(pairs: Sequence[tuple]) -> float:
    """Least-squares-through-origin scale k for measured ≈ k·predicted
    over ``(predicted_s, measured_s)`` pairs."""
    num = sum(m * p for p, m in pairs)
    den = sum(p * p for p, _ in pairs)
    return num / den if den > 0 else 0.0


def closure_report(sched, measured: Dict[str, float],
                   band_factor: float = BAND_FACTOR,
                   min_band_bytes: int = MIN_BAND_BYTES,
                   max_band_bytes: int = MAX_BAND_BYTES) -> dict:
    """Per-stage residual table + band verdict for one schedule.

    ``measured`` maps IR paths (``bucket[i].stage[j]``) to host
    seconds, as produced by :func:`measure_schedule`.

    Calibration is fitted PER PARTICIPANT COUNT (one k per distinct
    ``axis_size`` over that group's gated rows): the host-backend
    replays have strongly participant-count-dependent effective
    bandwidth (a p=2 permute is mostly memcpy; a p=8 one round-trips
    the scheduler per hop), a property the interconnect model
    deliberately does not encode.  Within one participant count the
    model's SIZE scaling must hold to within the band — that is the
    invariant the residuals gate, and only over the calibration
    regime ``[min_band_bytes, max_band_bytes]`` of wire bytes: below
    it host dispatch latency dominates, above it host cache/NUMA
    curvature does, and both are backend artifacts the model has no
    term for.  Out-of-regime stages are reported with their ratio but
    neither fitted nor gated.  ``calibration.k`` remains the global
    fit (all gated rows), which is what :func:`measured_timeline`
    uses to map measured seconds back into model units.
    """
    rows: List[dict] = []
    for path, _bucket, st in sched.iter_stages():
        if path not in measured:
            raise KeyError(f"no measurement for stage {path}")
        rows.append({
            "path": path, "op": st.op, "algorithm": st.algorithm,
            "axis": st.axis, "axis_size": int(st.axis_size),
            "n_bytes": int(st.n_bytes), "wire_bytes": int(st.wire_bytes),
            "codec": getattr(st, "codec", "none") or "none",
            "predicted_s": float(st.predicted_s),
            "measured_s": float(measured[path]),
            "gated": (min_band_bytes <= int(st.wire_bytes)
                      <= max_band_bytes),
        })
    fit = [r for r in rows if r["gated"]] or rows
    k = calibrate([(r["predicted_s"], r["measured_s"]) for r in fit])
    by_p: Dict[int, List[dict]] = {}
    for r in fit:
        by_p.setdefault(r["axis_size"], []).append(r)
    k_p = {p: calibrate([(r["predicted_s"], r["measured_s"])
                         for r in grp])
           for p, grp in by_p.items()}
    for r in rows:
        cal = k_p.get(r["axis_size"], k) * r["predicted_s"]
        r["calibrated_s"] = cal
        if cal > 0 and r["measured_s"] > 0:
            r["ratio"] = max(r["measured_s"] / cal, cal / r["measured_s"])
        else:
            r["ratio"] = float("inf")
    gated = [r for r in rows if r["gated"]]
    return {
        "band": {"factor": band_factor, "min_bytes": min_band_bytes,
                 "max_bytes": max_band_bytes},
        "calibration": {
            "k": k, "n_fit": len(fit),
            "per_axis_size": {str(p): {"k": k_p[p],
                                       "n_fit": len(by_p[p])}
                              for p in sorted(by_p)},
        },
        "stages": rows,
        "n_stages": len(rows),
        "n_gated": len(gated),
        "max_ratio": max((r["ratio"] for r in gated), default=0.0),
        "all_within_band": all(r["ratio"] <= band_factor for r in gated),
    }


def measured_timeline(sched, measured: Dict[str, float], k: float,
                      compute_s: float):
    """The §3.6 simulator replayed with MEASURED per-bucket latencies.

    Each bucket's comm time becomes the sum of its stages' measured
    host seconds mapped into model units through 1/k (the calibration
    inverse); readiness and the serialized-channel rules are unchanged.
    Comparing this timeline's ``overlap_fraction`` against the
    predicted one is the closure's end-to-end number.
    """
    from repro.core import overlap

    if k <= 0:
        raise ValueError(f"non-positive calibration k={k}")
    by_bucket: Dict[int, float] = {}
    for path, bucket, _st in sched.iter_stages():
        by_bucket[bucket.index] = \
            by_bucket.get(bucket.index, 0.0) + measured[path] / k
    backward_s = compute_s * overlap.BACKWARD_FRACTION
    tasks = [dataclasses.replace(t, comm_s=by_bucket[t.index])
             for t in overlap.schedule_tasks(sched, backward_s)]
    return overlap.simulate(
        tasks, backward_s,
        serial_s=compute_s * (1.0 - overlap.BACKWARD_FRACTION))


# ---------------------------------------------------------------------------
# the committed artifact (BENCH_telemetry.json)
# ---------------------------------------------------------------------------

ARTIFACT_DEVICES = 8
ARTIFACT_REPS = 5
ARTIFACT_BYTES = (1 << 20, 4 << 20, 16 << 20)


def artifact_cells() -> List[dict]:
    """The canonical cell set: both ppermute algorithms flat at p=8, an
    int8-coded wire, and a composed two-level schedule on a (2,4)
    pod×data mesh — every stage ``op`` and the codec path appear."""
    from repro.core import schedule as schedule_mod

    composed = f"ring_rsa{schedule_mod.SEP}rhd_rsa"
    cells = [
        {"name": "ring_rsa@8", "strategy": "ring_rsa", "codec": "none",
         "axis_names": ["data"], "axis_sizes": [8]},
        {"name": "rhd_rsa@8", "strategy": "rhd_rsa", "codec": "none",
         "axis_names": ["data"], "axis_sizes": [8]},
        {"name": "ring_rsa+int8@8", "strategy": "ring_rsa",
         "codec": "int8", "axis_names": ["data"], "axis_sizes": [8]},
        {"name": "ring×rhd@2x4", "strategy": composed, "codec": "none",
         "axis_names": ["pod", "data"], "axis_sizes": [2, 4]},
    ]
    for c in cells:
        c["bucket_bytes"] = list(ARTIFACT_BYTES)
        c["wire_dtype"] = "float32"
    return cells


def cell_schedule(cell: dict):
    """Rebuild a cell's DETACHED schedule from its recorded config —
    the same call at emit and at check time, so the predicted side is
    always the CURRENT cost model's."""
    from repro.core import schedule as schedule_mod

    return schedule_mod.synthetic(
        cell["bucket_bytes"], cell["strategy"],
        axis_sizes=tuple(cell["axis_sizes"]),
        axis_names=tuple(cell["axis_names"]),
        wire_dtype=cell["wire_dtype"], codec=cell["codec"])


_MEASURE_SNIPPET = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
os.environ.pop("REPRO_TRACE", None)
sys.path.insert(0, {src!r})
from repro.telemetry import closure
out = {{}}
for cell in closure.artifact_cells():
    sched = closure.cell_schedule(cell)
    out[cell["name"]] = closure.measure_schedule(sched, reps={reps})
print("RESULT " + json.dumps(out))
"""


def _measure_cells_subprocess(reps: int) -> Dict[str, Dict[str, float]]:
    """Measure the canonical cells in a child with forced host devices
    (the parent keeps its real device count — same discipline as
    benchmarks/allreduce_micro.py)."""
    src = os.path.join(_ROOT, "src")
    snippet = _MEASURE_SNIPPET.format(ndev=ARTIFACT_DEVICES, src=src,
                                      reps=reps)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", snippet], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"measure subprocess failed:\n{proc.stderr}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in:\n{proc.stdout}")


def build_artifact(measured_by_cell: Dict[str, Dict[str, float]],
                   reps: int = ARTIFACT_REPS) -> dict:
    cells_out = []
    for cell in artifact_cells():
        sched = cell_schedule(cell)
        report = closure_report(sched, measured_by_cell[cell["name"]])
        cells_out.append({**cell, **report})
    return {
        "schema": TELEMETRY_SCHEMA,
        "generated_by": "python -m repro.telemetry.closure --emit",
        "platform": "xla-force-host (CPU)",
        "devices": ARTIFACT_DEVICES,
        "reps": reps,
        "band": {"factor": BAND_FACTOR, "min_bytes": MIN_BAND_BYTES,
                 "max_bytes": MAX_BAND_BYTES},
        "cells": cells_out,
        "all_within_band": all(c["all_within_band"] for c in cells_out),
    }


def emit_artifact(path: str = TELEMETRY_ARTIFACT,
                  reps: int = ARTIFACT_REPS) -> dict:
    artifact = build_artifact(_measure_cells_subprocess(reps), reps=reps)
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    return artifact


def check_artifact(path: str = TELEMETRY_ARTIFACT) -> List[str]:
    """Currency problems with the committed closure artifact.

    Deliberately does NOT re-measure: it reloads the stored measured
    side, rebuilds the predicted side from the CURRENT cost model via
    :func:`cell_schedule`, and re-derives calibration and band
    verdicts.  A cost-model / decomposition / codec-accounting change
    therefore trips this check until the artifact is re-emitted.
    """
    problems: List[str] = []
    if not os.path.exists(path):
        return [f"{os.path.basename(path)} missing — run "
                f"python -m repro.telemetry.closure --emit"]
    try:
        with open(path) as f:
            art = json.load(f)
    except ValueError as e:
        return [f"{os.path.basename(path)}: unparseable JSON ({e})"]
    name = os.path.basename(path)
    if art.get("schema") != TELEMETRY_SCHEMA:
        return [f"{name}: schema {art.get('schema')!r} != "
                f"{TELEMETRY_SCHEMA}"]
    cells = art.get("cells", [])
    expected = {c["name"] for c in artifact_cells()}
    got = {c.get("name") for c in cells}
    if got != expected:
        problems.append(f"{name}: cell set {sorted(got)} != canonical "
                        f"{sorted(expected)} — re-emit")
        return problems
    if not any(c.get("codec", "none") != "none" for c in cells):
        problems.append(f"{name}: no codec'd cell")
    band = art.get("band", {})
    if band.get("factor") != BAND_FACTOR \
            or band.get("min_bytes") != MIN_BAND_BYTES \
            or band.get("max_bytes") != MAX_BAND_BYTES:
        problems.append(f"{name}: declared band {band} != current "
                        f"({BAND_FACTOR}, {MIN_BAND_BYTES}, "
                        f"{MAX_BAND_BYTES})")
    for cell in cells:
        sched = cell_schedule(cell)
        stored = {r["path"]: r for r in cell.get("stages", [])}
        fresh_paths = [p for p, _b, _s in sched.iter_stages()]
        if sorted(stored) != sorted(fresh_paths):
            problems.append(
                f"{name}: cell {cell['name']} stage paths drifted "
                f"(decomposition changed) — re-emit")
            continue
        measured = {}
        for p, _b, st in sched.iter_stages():
            row = stored[p]
            measured[p] = row["measured_s"]
            for field, current in (("predicted_s", float(st.predicted_s)),
                                   ("wire_bytes", int(st.wire_bytes))):
                ref = row.get(field)
                tol = 1e-9 * max(abs(current), 1e-30)
                if ref is None or abs(ref - current) > tol:
                    problems.append(
                        f"{name}: cell {cell['name']} {p}.{field} "
                        f"stored {ref} != current model {current} "
                        f"(cost model drifted) — re-emit")
        fresh = closure_report(sched, measured)
        if not fresh["all_within_band"]:
            bad = [r["path"] for r in fresh["stages"]
                   if r["gated"] and r["ratio"] > BAND_FACTOR]
            problems.append(
                f"{name}: cell {cell['name']} residuals out of band "
                f"against the current cost model: {bad}")
        if cell.get("all_within_band") is not True:
            problems.append(f"{name}: cell {cell['name']} committed "
                            f"with all_within_band != true")
    if art.get("all_within_band") is not True:
        problems.append(f"{name}: all_within_band != true")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measured-vs-predicted timeline closure artifact")
    ap.add_argument("--emit", nargs="?", const=TELEMETRY_ARTIFACT,
                    metavar="PATH",
                    help=f"measure the canonical cells (subprocess, "
                         f"{ARTIFACT_DEVICES} forced host devices) and "
                         f"write the artifact")
    ap.add_argument("--reps", type=int, default=ARTIFACT_REPS)
    ap.add_argument("--check", action="store_true",
                    help="validate the committed artifact against the "
                         "current cost model (no re-measure)")
    args = ap.parse_args(argv)
    if args.emit:
        art = emit_artifact(args.emit, reps=args.reps)
        print(f"wrote {args.emit}: {len(art['cells'])} cells, "
              f"all_within_band={art['all_within_band']}")
        return 0 if art["all_within_band"] else 1
    problems = check_artifact()
    for p in problems:
        print(f"PROBLEM: {p}")
    if not problems:
        print(f"{os.path.basename(TELEMETRY_ARTIFACT)} current")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
