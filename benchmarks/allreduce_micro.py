"""Paper Figs. 4 & 6: Allreduce latency vs message size per design.

Three complementary modes:
  * analytic — α-β(-γ) model on TPU v5e constants for: MPI (default,
    host-staged reduction), MPI-Opt (the paper's RHD + on-chip kernel
    reduction), NCCL2 analogue (vendor psum), ring (Baidu), PS (gRPC).
  * analytic non-pow2 — RHD vs ring over the paper's actual cluster
    shapes (6-, 12-, 24-way): the MVAPICH2 pre/post fold costs +2 steps
    and +2·N bytes but keeps the 2·log2(core) step count that wins on
    latency-bound messages.
  * measured — wall-clock of the actual ppermute implementations on XLA
    host devices, including non-pow2 submeshes p ∈ {3, 6, 12}
    (semantics identical to TPU; absolute numbers are CPU-bound,
    relative step-count effects are visible). Runs in a subprocess so
    the main process keeps one device.

Tuning-table emission (MVAPICH2-style, DESIGN.md §3.5):

    python benchmarks/allreduce_micro.py --emit-table out.json \
        [--table-mode measured|analytic] [--table-ps 3,4,6,8] \
        [--table-sizes 1024,65536,...]

writes a schema-validated JSON table that the EMPIRICAL selector
(`repro.core.selector`, ``AggregatorConfig(strategy="auto",
selector_mode="empirical", selector_table=...)``) loads back.  A full
default-grid MEASURED run additionally refreshes the repo-root
``BENCH_allreduce.json`` trajectory artifact (same schema, plus a
``meta`` block with the analytic crossovers so the measured-vs-modeled
story is tracked across PRs); ad-hoc subsets never touch it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.core import cost_model as cm
from repro.core import selector as sel
from repro.core.reducers import allreduce_steps, wire_bytes

SIZES = [8, 1024, 64 * 1024, 1 << 20, 16 << 20, 64 << 20, 256 << 20]
P_DEVICES = 16
NONPOW2_P = [3, 6, 12, 24]

# Tuning-table defaults: the host shapes the measured mode can actually
# run (pow2 and non-pow2), and a size ladder spanning the latency-bound
# to bandwidth-bound regimes.
TABLE_PS = [3, 4, 6, 8, 12]
TABLE_SIZES = [1024, 16 * 1024, 256 * 1024, 1 << 20, 8 << 20]
# Multi-axis (pod × data) host meshes for the composed two-level sweep:
# (pods, d) with d×pods ∈ {2×3, 4×2, 2×4} — 6/8/8 devices.  Each mesh
# measures the flat folds AND the composed ring_rsa×{rhd_rsa, ring_rsa,
# psum} schedules (core/schedule.py decomposition trees), emitted as
# "axes" entries so the empirical selector can prefer a composition
# per bucket on multi-axis meshes.
TABLE_MESHES = [(3, 2), (2, 4), (4, 2)]
MULTIAXIS_STRATEGIES = ["psum", "ring_rsa", "rhd_rsa",
                        "ring_rsa×rhd_rsa", "ring_rsa×ring_rsa",
                        "ring_rsa×psum"]
BENCH_ARTIFACT = os.path.join(os.path.dirname(__file__), "..",
                              "BENCH_allreduce.json")

# Wire-codec sweep (--codec, and the full-grid BENCH refresh): the
# codec-bearing algorithms at the message sizes where the α-β-γ model
# says the encoded wire should win.  On the simulated host platform the
# "wire" (ppermute memcpy) and the quantize compute SERIALIZE onto the
# same cores, so the β-dominated speedup the model predicts for a real
# link compresses toward 1x — the hard, deterministic form of the
# bandwidth win (4x fewer encoded bytes on the wire) is therefore
# proven exactly by the HLO byte cross-check in
# tests/multidev_codec_checks.py, and what this sweep gates is model
# AGREEMENT: for ring_rsa at the bandwidth-bound end (largest size),
# measured and predicted speedup must agree within a two-sided
# CODEC_BAND_FACTOR corridor.  rhd_rsa rows are recorded as data but
# not band-checked: its halving steps recompute the absmax over the
# full remaining half each hop, which on CPU swamps the wire saving
# the model prices.  fp8_e4m3 rows are likewise data-only: XLA
# software-emulates float8 casts on CPU (a free hardware cast on TPU),
# so its host cells measure the emulation, not the wire.
CODEC_P = 8
CODEC_SIZES = [1 << 20, 8 << 20, 32 << 20]
CODEC_STRATEGIES = ["ring_rsa", "rhd_rsa"]
CODEC_BAND_STRATEGY = "ring_rsa"
CODEC_BAND_CODECS = ("bf16", "int8")
CODEC_BAND_FACTOR = 3.0

# Fused-hop sweep (--fused-hops, and the full-grid BENCH refresh): the
# same schedule executed through BOTH routes — unfused (per-call jitted
# shard_map per bucket, the pre-§3.13 path) vs fused (the cached
# donated StageExecutor whose hops run the fused decode→accumulate→
# encode kernel) — via telemetry.closure.measure_fused_replay.  The
# gate is one-sided with a noise corridor: fused must be NO SLOWER
# anywhere (speedup >= 1/FUSED_NOISE_FACTOR) and strictly faster on at
# least one codec'd cell (speedup >= FUSED_NOISE_FACTOR).
#
# Cells are (n_buckets, bytes_per_bucket).  The single-bucket cells
# pin ROUTE PARITY: on this host the direct-lowered kernels compile to
# the same HLO as the staged walk, so fused must hold ~1.0x (the
# kernel-level win is a TPU/Mosaic effect this backend cannot show).
# The multi-bucket cell is where the EXECUTOR wins on any backend —
# one jitted program walks every bucket per call (XLA schedules the
# per-bucket collectives together) where the unfused route pays one
# dispatch per bucket — the paper's pointer-cache design point:
# GDR-Opt's gain is amortizing per-call overheads, not just the
# kernel.  Bucket counts stay small: XLA CPU's optimization time on
# one program holding N stage walks grows superlinearly in N (a
# 16-bucket ring cell compiles for minutes).
FUSED_P = CODEC_P
FUSED_CELLS = [(1, 1 << 20), (1, 8 << 20), (6, 64 << 10)]
FUSED_CODECS = ["none", "bf16", "int8"]
FUSED_STRATEGIES = ["ring_rsa", "rhd_rsa"]
# 8 emulated host devices share this machine's cores with the OS:
# identical cells jitter ±10% between runs even with interleaved
# best-of-reps timing, so the corridor must clear that floor or the
# gate flaps (observed: a cell flipping 0.89x <-> 1.05x run to run)
FUSED_NOISE_FACTOR = 1.15


def analytic_nonpow2_rows():
    """RHD vs ring over non-pow2 device counts (the 6-/12-/24-way
    shapes the paper characterizes): step/byte truth plus model latency
    at a latency-bound (1KB) and a bandwidth-bound (16MB) size."""
    rows = []
    for p in NONPOW2_P:
        for n in (1024, 16 << 20):
            rows.append({
                "p": p,
                "bytes": n,
                "rhd_steps": allreduce_steps("rhd_rsa", p),
                "ring_steps": allreduce_steps("ring_rsa", p),
                "rhd_wire_bytes": wire_bytes("rhd_rsa", n, p),
                "ring_wire_bytes": wire_bytes("ring_rsa", n, p),
                "rhd_us": cm.allreduce_latency("rhd_rsa", n, p) * 1e6,
                "ring_us": cm.allreduce_latency("ring_rsa", n, p) * 1e6,
            })
    return rows


def analytic_rows():
    rows = []
    for n in SIZES:
        mpi_def = cm.allreduce_latency_host_staged("rhd_rsa", n, P_DEVICES)
        mpi_opt = cm.allreduce_latency("rhd_rsa", n, P_DEVICES)
        ring = cm.allreduce_latency("ring_rsa", n, P_DEVICES)
        vendor = cm.allreduce_latency("psum", n, P_DEVICES)
        ps = cm.allreduce_latency("ps_gather", n, P_DEVICES)
        rows.append({
            "bytes": n,
            "MPI_default_us": mpi_def * 1e6,
            "MPI_Opt_us": mpi_opt * 1e6,
            "ring_us": ring * 1e6,
            "NCCL2_us": vendor * 1e6,
            "PS_us": ps * 1e6,
            "opt_vs_default": mpi_def / mpi_opt,
            "opt_vs_vendor": vendor / mpi_opt,
        })
    return rows


_MEASURE_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys, time, json
sys.path.insert(0, {src!r})
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import reducers
from repro.core.compat import shard_map

devs = jax.devices()
out = []
for p in {device_counts!r}:
    mesh = Mesh(np.array(devs[:p]), ("data",))
    for n_bytes in {sizes!r}:
        n = max(n_bytes // 4, 1)
        x = jnp.ones((p * n,), jnp.float32)
        row = {{"p": p, "bytes": n_bytes}}
        for strat in ["psum", "ring_rsa", "rhd_rsa", "ps_gather"]:
            fn = jax.jit(shard_map(
                lambda xl: reducers.allreduce(xl, ("data",), strat),
                mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={{"data"}}, check_vma=False))
            r = fn(x); r.block_until_ready()
            reps = 20 if n_bytes < (1 << 20) else 5
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(x)
            r.block_until_ready()
            row[strat + "_us"] = (time.perf_counter() - t0) / reps * 1e6
        out.append(row)
print(json.dumps(out))
"""


def measured_rows(sizes=None, device_counts=(8,)):
    """Wall-clock the real reducers on XLA host submeshes of the first
    ``p`` devices for each ``p`` in ``device_counts`` (non-pow2 welcome:
    the RHD pre/post fold runs for p=3/6/12)."""
    sizes = sizes or [8, 64 * 1024, 1 << 20, 16 << 20]
    ndev = max(device_counts)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _MEASURE_SNIPPET.format(src=os.path.abspath(src), sizes=sizes,
                                   ndev=ndev,
                                   device_counts=list(device_counts))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


_MEASURE_MULTIAXIS_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys, time, json
sys.path.insert(0, {src!r})
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import reducers
from repro.core import schedule as S
from repro.core.compat import shard_map

devs = jax.devices()
out = []
for pods, d in {meshes!r}:
    p = pods * d
    mesh = Mesh(np.array(devs[:p]).reshape(pods, d), ("pod", "data"))
    for n_bytes in {sizes!r}:
        n = max(n_bytes // 4, 1)
        x = jnp.ones((p * n,), jnp.float32)
        row = {{"p": p, "axes": [pods, d], "bytes": n_bytes,
                "latency_us": {{}}}}
        for strat in {strategies!r}:
            stages = S.decompose(strat, n_bytes, ("pod", "data"),
                                 (pods, d))
            fn = jax.jit(shard_map(
                lambda xl: reducers.execute_stages(xl, stages),
                mesh, in_specs=P(("pod", "data")),
                out_specs=P(("pod", "data")),
                axis_names={{"pod", "data"}}, check_vma=False))
            r = fn(x); r.block_until_ready()
            reps = 20 if n_bytes < (1 << 20) else 5
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(x)
            r.block_until_ready()
            row["latency_us"][strat] = \
                (time.perf_counter() - t0) / reps * 1e6
        out.append(row)
print(json.dumps(out))
"""


def measured_multiaxis_rows(sizes=None, meshes=None):
    """Wall-clock flat folds and composed two-level schedules on
    (pod × data) host meshes — executed stage-by-stage through the SAME
    ``reducers.execute_stages`` path the aggregator uses for a resolved
    ReduceSchedule."""
    sizes = sizes or TABLE_SIZES
    meshes = [tuple(m) for m in (meshes or TABLE_MESHES)]
    ndev = max(pods * d for pods, d in meshes)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _MEASURE_MULTIAXIS_SNIPPET.format(
        src=os.path.abspath(src), sizes=list(sizes), ndev=ndev,
        meshes=meshes, strategies=MULTIAXIS_STRATEGIES)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


_MEASURE_CODEC_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys, time, json
sys.path.insert(0, {src!r})
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import reducers
from repro.core import schedule as S
from repro.core.compat import shard_map

p = {p}
devs = jax.devices()
mesh = Mesh(np.array(devs[:p]), ("data",))
out = []
for codec in {codecs!r}:
    for n_bytes in {sizes!r}:
        n = max(n_bytes // 4, 1)
        x = jnp.ones((p * n,), jnp.float32)
        row = {{"p": p, "bytes": n_bytes, "codec": codec,
                "latency_us": {{}}}}
        for strat in {strategies!r}:
            stages = S.decompose(strat, n_bytes, ("data",), (p,),
                                 codec=codec)
            fn = jax.jit(shard_map(
                lambda xl: reducers.execute_stages(xl, stages),
                mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={{"data"}}, check_vma=False))
            r = fn(x); r.block_until_ready()
            # best-of-reps (not mean): speedup RATIOS are what the band
            # asserts, and host-CPU contention spikes poison a mean
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                r = fn(x)
                r.block_until_ready()
                best = min(best, time.perf_counter() - t0)
            row["latency_us"][strat] = best * 1e6
        out.append(row)
print(json.dumps(out))
"""


_MEASURE_FUSED_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys, json
sys.path.insert(0, {src!r})
from repro.core import schedule as S
from repro.telemetry import closure

p = {p}
out = []
for codec in {codecs!r}:
    for n_buckets, n_bytes in {cells!r}:
        for strat in {strategies!r}:
            sched = S.synthetic([n_bytes] * n_buckets, strat, (p,),
                                axis_names=("data",), codec=codec)
            rep = closure.measure_fused_replay(sched, reps={reps})
            out.append({{"p": p, "bytes": n_bytes,
                         "buckets": n_buckets, "codec": codec,
                         "strategy": strat,
                         "fused_us": rep["fused_s"] * 1e6,
                         "unfused_us": rep["unfused_s"] * 1e6,
                         "speedup": rep["speedup"],
                         "residual_rel": rep["residual_rel"],
                         "executor_traces": rep["executor_traces"]}})
print(json.dumps(out))
"""


def measured_fused_rows(cells=None, p=FUSED_P, codecs=None,
                        strategies=None, reps=7):
    """Wall-clock fused-vs-unfused execution of the SAME schedules via
    ``telemetry.closure.measure_fused_replay`` (subprocess, forced host
    devices — same discipline as every other sweep here).  ``cells``
    is a list of ``(n_buckets, bytes_per_bucket)``."""
    cells = [(int(nb), int(b)) for nb, b in (cells or FUSED_CELLS)]
    codecs = list(codecs or FUSED_CODECS)
    strategies = list(strategies or FUSED_STRATEGIES)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _MEASURE_FUSED_SNIPPET.format(
        src=os.path.abspath(src), ndev=p, p=p, cells=cells,
        codecs=codecs, strategies=strategies, reps=reps)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=1800,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def fused_report(rows, noise_factor=FUSED_NOISE_FACTOR) -> dict:
    """Fused-route verdict from ``measured_fused_rows`` output: every
    cell must be no slower than 1/``noise_factor`` and at least one
    codec'd cell must be faster than ``noise_factor`` (the paper's
    GDR-Opt claim shape: the fused kernel wins where the wire is
    coded, and never loses elsewhere)."""
    out = []
    for r in rows:
        out.append({
            "p": int(r["p"]), "bytes": int(r["bytes"]),
            "buckets": int(r.get("buckets", 1)),
            "codec": r["codec"], "strategy": r["strategy"],
            "fused_us": round(float(r["fused_us"]), 1),
            "unfused_us": round(float(r["unfused_us"]), 1),
            "speedup": round(float(r["speedup"]), 3),
            "residual_rel": float(r["residual_rel"]),
            "executor_traces": int(r["executor_traces"]),
            "no_slower": float(r["speedup"]) >= 1.0 / noise_factor,
        })
    return {
        "noise_factor": noise_factor,
        "rows": out,
        "no_slower_everywhere": all(r["no_slower"] for r in out),
        "faster_codec_cell": any(
            r["codec"] != "none" and r["speedup"] >= noise_factor
            for r in out),
    }


def default_codecs() -> list[str]:
    """Every registered wire codec the running jax can encode."""
    from repro.core import codec as codec_mod
    return [c for c in codec_mod.CODECS if c != "none"
            and codec_mod.available(c)]


def measured_codec_rows(sizes=None, p=CODEC_P, codecs=None,
                        strategies=None):
    """Wall-clock codec'd vs uncoded schedules through the SAME
    ``decompose`` + ``execute_stages`` path the aggregator runs.  A
    ``codec="none"`` baseline row is always included (it feeds the
    speedup report, NOT the tuning entries — the flat sweep already
    covers uncoded latencies)."""
    sizes = list(sizes or CODEC_SIZES)
    codecs = ["none"] + [c for c in (codecs or default_codecs())
                         if c != "none"]
    strategies = list(strategies or CODEC_STRATEGIES)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _MEASURE_CODEC_SNIPPET.format(
        src=os.path.abspath(src), ndev=p, p=p, sizes=sizes,
        codecs=codecs, strategies=strategies)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=1800,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def codec_report(rows, band_strategy=CODEC_BAND_STRATEGY,
                 band_codecs=CODEC_BAND_CODECS,
                 band_factor=CODEC_BAND_FACTOR) -> dict:
    """Measured-vs-modeled codec speedups from ``measured_codec_rows``
    output: per (bytes, codec, strategy) the measured speedup over the
    codec="none" baseline next to the cost model's prediction.  The
    ``within_band`` verdict applies at the bandwidth-bound end (largest
    size) of ``band_strategy`` × ``band_codecs`` only (see the CODEC_*
    comments above for why rhd/fp8 host cells are data, not gates)."""
    from repro.core import schedule as S
    base = {(r["bytes"], s): r["latency_us"][s]
            for r in rows if r["codec"] == "none"
            for s in r["latency_us"]}
    top = max(r["bytes"] for r in rows)
    out = []
    for r in rows:
        if r["codec"] == "none":
            continue
        p = r["p"]
        for strat, us in sorted(r["latency_us"].items()):
            measured = base[(r["bytes"], strat)] / us
            predicted = (S.strategy_latency(strat, r["bytes"], (p,))
                         / S.strategy_latency(strat, r["bytes"], (p,),
                                              codec=r["codec"]))
            rec = {"p": p, "bytes": r["bytes"], "codec": r["codec"],
                   "strategy": strat,
                   "measured_speedup": round(measured, 3),
                   "predicted_speedup": round(predicted, 3)}
            if strat == band_strategy and r["bytes"] == top \
                    and r["codec"] in band_codecs:
                ratio = max(predicted / measured, measured / predicted)
                rec["within_band"] = ratio <= band_factor
            out.append(rec)
    return {"band_strategy": band_strategy, "band_factor": band_factor,
            "band_codecs": list(band_codecs), "rows": out,
            "all_within_band": all(r["within_band"] for r in out
                                   if "within_band" in r)}


def measured_tuning_entries(ps=None, sizes=None):
    """Measured-mode tuning entries: wall-clock each strategy on real
    XLA host submeshes — the MVAPICH2 way (run on the deployment
    platform; here that is host CPU, DESIGN.md D1)."""
    ps = list(ps or TABLE_PS)
    sizes = list(sizes or TABLE_SIZES)
    entries = []
    for row in measured_rows(sizes=sizes, device_counts=tuple(ps)):
        entries.append({
            "p": int(row["p"]), "bytes": int(row["bytes"]),
            "latency_us": {k[:-3]: float(v) for k, v in row.items()
                           if k.endswith("_us")},
        })
    return entries


def build_tuning_table(mode="measured", ps=None, sizes=None,
                       meshes=None, codec_sweep=False,
                       fused_sweep=False) -> dict:
    ps = list(ps or TABLE_PS)
    sizes = list(sizes or TABLE_SIZES)
    if mode == "analytic":
        table = sel.build_analytic_table(ps, sizes, link=cm.ICI)
        table["meta"] = {"mode": "analytic", "link": "ici"}
    elif mode == "measured":
        entries = measured_tuning_entries(ps, sizes)
        meshes = [list(m) for m in (meshes if meshes is not None
                                    else TABLE_MESHES)]
        if meshes:
            # composed two-level sweep on (pod × data) host meshes —
            # "axes" entries the empirical selector matches exactly
            entries += measured_multiaxis_rows(sizes=sizes,
                                               meshes=meshes)
        table = {"schema": sel.TABLE_SCHEMA, "link": "host-cpu",
                 "entries": entries,
                 "meta": {"mode": "measured", "platform": "xla-host-cpu",
                          "meshes": meshes}}
        if codec_sweep:
            # codec'd rows become "codec" entries (the empirical
            # selector keyed per codec); the none-baseline rows feed
            # only the measured-vs-modeled speedup report in meta
            crows = measured_codec_rows()
            entries += [r for r in crows if r["codec"] != "none"]
            table["meta"]["codec"] = codec_report(crows)
        if fused_sweep:
            # fused-vs-unfused rows live in meta only: the tuning
            # entries measure WHICH algorithm to pick, the fused report
            # measures HOW to execute it (two routes, same schedule)
            table["meta"]["fused"] = fused_report(measured_fused_rows())
    else:
        raise ValueError(f"table mode {mode!r}; one of analytic|measured")
    table["meta"].update({
        "ps": ps, "sizes": sizes,
        # analytic crossover trajectory: where the model says RHD stops
        # winning, per p (inf = always wins; tracked across PRs in
        # BENCH_allreduce.json)
        "analytic_crossover_bytes": {
            str(p): (None if cross == float("inf") else int(cross))
            for p, cross in ((p, sel.crossover_bytes(p, link=cm.ICI))
                             for p in ps)},
        # ... and the fused-hop re-pricing: the coded crossovers under
        # the fused γ (cost_model.quant_gamma(fused=True)) — RHD's
        # reign extends when its heavier quantize toll is fused away
        # (tests/test_selector.py pins the direction)
        "fused_crossover_bytes": {
            str(p): (None if cross == float("inf") else int(cross))
            for p, cross in ((p, sel.crossover_bytes(
                p, link=cm.ICI, codec="int8", fused=True))
                for p in ps)},
    })
    sel.validate_table(table)
    return table


def emit_table(path: str, mode="measured", ps=None, sizes=None,
               artifact: str | None = None,
               codec_sweep: bool | None = None,
               fused_sweep: bool | None = None) -> dict:
    """Write the tuning table to ``path``; when ``artifact`` is set,
    also refresh the repo-root BENCH_allreduce.json trajectory artifact
    (both are valid empirical-selector inputs). The caller only passes
    ``artifact`` for full default-grid runs — an ad-hoc --table-ps/
    --table-sizes subset must never silently rewrite the tracked
    trajectory.  The codec and fused-hop sweeps default to exactly
    those artifact runs (the tracked trajectory must always carry the
    codec and fused-execution stories)."""
    if codec_sweep is None:
        codec_sweep = bool(artifact) and mode == "measured"
    if fused_sweep is None:
        fused_sweep = bool(artifact) and mode == "measured"
    table = build_tuning_table(mode, ps, sizes, codec_sweep=codec_sweep,
                               fused_sweep=fused_sweep)
    sel.save_table(table, path)
    if artifact:
        sel.save_table(table, artifact)
    return table


def _record_measured_rows(rows, sweep: str):
    """Mirror a measured sweep into the telemetry registry (no-op when
    telemetry is off): per-strategy latency histograms, so a traced
    benchmark run snapshots the same numbers the CSV lines print."""
    from repro import telemetry
    if not telemetry.enabled():
        return
    h = telemetry.METRICS.histogram(
        "allreduce_measured_us",
        help="measured allreduce latency (µs) by sweep/strategy/p")
    for r in rows:
        p = r.get("p") or "x".join(str(a) for a in r.get("axes", ()))
        for k, v in r.items():
            if k.endswith("_us") and not isinstance(v, dict):
                h.observe(float(v), sweep=sweep, strategy=k[:-3], p=p)
        for s, v in (r.get("latency_us") or {}).items():
            h.observe(float(v), sweep=sweep, strategy=s, p=p)


def run(csv=True, measure=True):
    from repro import telemetry
    tracer = telemetry.get_tracer()
    rows = analytic_rows()
    lines = []
    for r in rows:
        lines.append(f"allreduce_micro.analytic.MPI_default,"
                     f"{r['MPI_default_us']:.2f},bytes={r['bytes']}")
        lines.append(f"allreduce_micro.analytic.MPI_Opt,"
                     f"{r['MPI_Opt_us']:.2f},bytes={r['bytes']} "
                     f"opt_vs_default={r['opt_vs_default']:.1f}x "
                     f"opt_vs_vendor={r['opt_vs_vendor']:.1f}x")
        lines.append(f"allreduce_micro.analytic.NCCL2,"
                     f"{r['NCCL2_us']:.2f},bytes={r['bytes']}")
        lines.append(f"allreduce_micro.analytic.PS,"
                     f"{r['PS_us']:.2f},bytes={r['bytes']}")
    for r in analytic_nonpow2_rows():
        lines.append(
            f"allreduce_micro.nonpow2.rhd,{r['rhd_us']:.2f},"
            f"p={r['p']} bytes={r['bytes']} steps={r['rhd_steps']} "
            f"wire={r['rhd_wire_bytes']}")
        lines.append(
            f"allreduce_micro.nonpow2.ring,{r['ring_us']:.2f},"
            f"p={r['p']} bytes={r['bytes']} steps={r['ring_steps']} "
            f"wire={r['ring_wire_bytes']}")
    if measure:
        with tracer.span("bench.measure.flat", cat="wall",
                         device_counts=[3, 6, 8, 12]) as sp:
            flat = measured_rows(device_counts=(3, 6, 8, 12))
            sp.set("n_rows", len(flat))
        _record_measured_rows(flat, "flat")
        for r in flat:
            for k, v in r.items():
                if k.endswith("_us"):
                    lines.append(f"allreduce_micro.measured.{k[:-3]},"
                                 f"{v:.1f},p={r['p']} bytes={r['bytes']}"
                                 f" host-cpu")
        # composed two-level schedules on (pod × data) meshes
        with tracer.span("bench.measure.multiaxis", cat="wall") as sp:
            multi = measured_multiaxis_rows(sizes=[64 * 1024, 1 << 20])
            sp.set("n_rows", len(multi))
        _record_measured_rows(multi, "multiaxis")
        for r in multi:
            pods, d = r["axes"]
            for s, v in r["latency_us"].items():
                lines.append(f"allreduce_micro.multiaxis.{s},"
                             f"{v:.1f},axes={pods}x{d} "
                             f"bytes={r['bytes']} host-cpu")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-table", metavar="OUT.json",
                    help="write an MVAPICH2-style tuning table for the "
                         "empirical selector (also refreshes "
                         "BENCH_allreduce.json)")
    ap.add_argument("--table-mode", default="measured",
                    choices=["measured", "analytic"])
    ap.add_argument("--table-ps", default="",
                    help="comma-separated device counts (default "
                         f"{TABLE_PS})")
    ap.add_argument("--table-sizes", default="",
                    help="comma-separated message bytes (default "
                         f"{TABLE_SIZES})")
    ap.add_argument("--no-measure", action="store_true",
                    help="skip the wall-clock sweep in the default run")
    ap.add_argument("--codec", action="store_true",
                    help="wall-clock the wire-codec sweep (codec'd vs "
                         "uncoded ring/RHD through execute_stages) and "
                         "print measured-vs-modeled speedups")
    ap.add_argument("--fused-hops", action="store_true",
                    help="wall-clock the fused-hop sweep (kernel-fused "
                         "decode+accumulate+encode executors vs the "
                         "stage-by-stage walk, same schedules) and "
                         "print measured speedups")
    ap.add_argument("--trace", metavar="OUT.json",
                    help="enable telemetry for this run and write a "
                         "Perfetto-loadable trace (repro/trace/v1) plus "
                         "a metrics snapshot next to it")
    args = ap.parse_args(argv)

    from repro import telemetry
    if args.trace:
        telemetry.configure(telemetry.TelemetryConfig(enabled=True))

    if args.codec:
        with telemetry.get_tracer().span("bench.measure.codec",
                                         cat="wall") as sp:
            rows = measured_codec_rows()
            sp.set("n_rows", len(rows))
        _record_measured_rows(rows, "codec")
        rep = codec_report(rows)
        for r in rep["rows"]:
            band = ""
            if "within_band" in r:
                band = (" within-band" if r["within_band"]
                        else " OUT-OF-BAND")
            print(f"allreduce_micro.codec.{r['strategy']}.{r['codec']},"
                  f"{r['measured_speedup']:.2f}x,"
                  f"bytes={r['bytes']} p={r['p']} "
                  f"predicted={r['predicted_speedup']:.2f}x{band}")
        print(f"allreduce_micro.codec.all_within_band,"
              f"{int(rep['all_within_band'])},band_factor="
              f"{rep['band_factor']} strategy={rep['band_strategy']}")
        _write_trace(args.trace)
        return

    if args.fused_hops:
        with telemetry.get_tracer().span("bench.measure.fused",
                                         cat="wall") as sp:
            rows = measured_fused_rows()
            sp.set("n_rows", len(rows))
        _record_measured_rows(rows, "fused")
        rep = fused_report(rows)
        for r in rep["rows"]:
            verdict = " no-slower" if r["no_slower"] else " SLOWER"
            print(f"allreduce_micro.fused.{r['strategy']}.{r['codec']},"
                  f"{r['speedup']:.2f}x,"
                  f"bytes={r['buckets']}x{r['bytes']} p={r['p']} "
                  f"traces={r['executor_traces']}{verdict}")
        print(f"allreduce_micro.fused.no_slower_everywhere,"
              f"{int(rep['no_slower_everywhere'])},noise_factor="
              f"{rep['noise_factor']}")
        print(f"allreduce_micro.fused.faster_codec_cell,"
              f"{int(rep['faster_codec_cell'])}")
        _write_trace(args.trace)
        return

    if args.emit_table:
        ps = [int(x) for x in args.table_ps.split(",")] \
            if args.table_ps else None
        sizes = [int(x) for x in args.table_sizes.split(",")] \
            if args.table_sizes else None
        # only a full default-grid MEASURED run refreshes the tracked
        # trajectory artifact; subsets/analytic runs just write `path`
        full_grid = ps is None and sizes is None
        artifact = BENCH_ARTIFACT if (full_grid and
                                      args.table_mode == "measured") \
            else None
        table = emit_table(args.emit_table, mode=args.table_mode,
                           ps=ps, sizes=sizes, artifact=artifact)
        where = args.emit_table
        if artifact:
            where += f" and {os.path.normpath(BENCH_ARTIFACT)}"
        print(f"wrote {len(table['entries'])} entries "
              f"({args.table_mode}) to {where}")
        _write_trace(args.trace)
        return
    print("\n".join(run(measure=not args.no_measure)))
    _write_trace(args.trace)


def _write_trace(path):
    """Export the run's trace + metrics snapshot when --trace was given
    (the spans wrap the subprocess sweeps: host wall-clock of each
    measurement pass, with row counts and per-row latencies mirrored
    into the metrics registry)."""
    if not path:
        return
    from repro import telemetry
    telemetry.get_tracer().write(path)
    print(f"wrote trace to {path}")
    print(telemetry.METRICS.render())


if __name__ == "__main__":
    main()
