"""The on-chip training benchmark: one cell of ``BENCHMARK.json`` per
run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<name>.json``, its family's
weights, FLOPs and plain reference in ``families/<family>.py``) and a
job mix (``mixes/<traffic>.json``: batch, sequence, mesh, optimizer,
aggregator).  Its correctness limits are in ``limits/<cell>.json`` and
each metric's reader is ``metrics/<metric>.py``.  Nothing here names a
cell, so a new cell is new files and entries.

One run, in one process holding the cell's chips:

  set-up   weights and optimizer state from ``--seed`` in one jitted
           call, a pool of distinct batches in another, and the
           program's train step (``Trainer.step_fn``) driven through
           its first steps, which compile it and are checked below;
  window   the same step object, dispatched asynchronously over the
           pool for ``--seconds`` (``--trace 0``), or a few steps under
           the profiler (``--trace 1``);
  check    after the window and with the program's state freed, the
           plain float32 reference repeats the first steps from the same
           seed; losses, the first gradient and the parameters' change
           are compared with the limits.

The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, inside the checkout, so that the next run of a cell here finds
# what this one compiled.
DEFAULT_CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# Harness spans in the profiler's host trace; the idle gaps are labelled
# by them.
HOST_LABELS = ("step", "data", "dispatch", "sync")
# A leaf whose reference gradient is under this share of the median
# leaf's moves under AdamW by rounding alone: its change is not compared.
NOUGHT_GRAD_SHARE = 1e-3
# The numbers ``compare`` gives; a cell's limits file holds some of them.
CHECKS = ("loss_gap", "first_loss_gap", "grad_gap", "grad_err",
          "delta_gap")
# Steps queued behind the one just dispatched, as a trainer keeps them.
IN_FLIGHT = 2
# Share of each batch's tokens replaced at random, so no row repeats.
TOKEN_NOISE = 0.05
# Steady steps under the profiler in a ``--trace 1`` run.
TRACE_STEPS = 6


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# the cell, from the files named in BENCHMARK.json
# ---------------------------------------------------------------------------

def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    family: Any
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def global_batch(self) -> int:
        return self.mix["batch_per_chip"] * self.chips

    @property
    def seq(self) -> int:
        return self.mix["seq_len"]

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.seq

    @property
    def flops_per_step(self) -> float:
        return self.family.train_flops_per_token(self.cfg, self.seq) \
            * self.tokens_per_step


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "mixes", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    limits_path = os.path.join(HERE, "limits", name + ".json")
    limits = {}
    if os.path.exists(limits_path):
        with open(limits_path) as f:
            limits = json.load(f)
    mesh = mix["mesh"]
    if mesh["data"] * mesh["model"] != w["chips"]:
        raise ValueError(f"{name}: mesh {mesh} does not use the cell's "
                         f"{w['chips']} chips")
    family = _load_module(
        os.path.join(HERE, "families", cfg["family"] + ".py"),
        "family_" + cfg["family"])
    return Cell(name, w["chips"], cfg, mix, family, limits,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def require_devices(n: int):
    """The cell's chips, or NoChip: a run never falls back to the CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX reports {len(devs)} "
                     f"{devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX reports {len(devs)}")
    return devs


def configure_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``.  Every program is
    cached, the sub-second ones too."""
    path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compile seconds and count, and persistent-cache lookups,
    hits and writes, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.lookups = self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1


# ---------------------------------------------------------------------------
# inputs from the seed
# ---------------------------------------------------------------------------

def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two words of a threefry key; passed
    to the jitted makers as data, so every seed runs the same program."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _key(words, stream: int):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    return jax.random.fold_in(key, stream)


def weights(words, cell: Cell):
    return cell.family.init_params(_key(words, 0), cell.cfg)


def batches(words, cell: Cell, first: int, count: int) -> list:
    """``count`` distinct batches of the global batch, numbered from
    ``first``: a noisy affine token recurrence (t[i+1] = t[i] + 17 mod V,
    a share ``TOKEN_NOISE`` of tokens replaced at random), labels the
    next token."""
    v, s, gb = cell.cfg["vocab_size"], cell.seq, cell.global_batch
    out = []
    for i in range(first, first + count):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(_key(words, 1), i),
                                      3)
        t0 = jax.random.randint(k1, (gb, 1), 0, v)
        toks = (t0 + jnp.arange(s + 1)[None, :] * 17) % v
        flip = jax.random.bernoulli(k2, TOKEN_NOISE, (gb, s + 1))
        rand = jax.random.randint(k3, (gb, s + 1), 0, v)
        toks = jnp.where(flip, rand, toks).astype(jnp.int32)
        out.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    return out


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    """What the window drives: ``step(params, opt_state, batch) ->
    (params, opt_state, metrics)`` and the shardings it takes."""
    step: Callable
    param_sh: Any
    opt_sh: Any
    batch_sh: Any
    opt_init: Callable
    hlo_text: Callable | None = None
    makers: Any = None          # its ``Makers``, made on first use


def _optimizer(mix: dict):
    from repro.optim import adamw

    o = dict(mix["optimizer"])
    if o.pop("name") != "adamw":
        raise ValueError(f"optimizer {mix['optimizer']['name']!r}: the "
                         "harness reads AdamW's first moment")
    lr = o.pop("lr")
    return adamw(lr, **o)


def build_program(cell: Cell, devices, fault: str = "") -> Program:
    """The program's own train step, as a user builds it: ``Trainer``
    over ``make_train_step`` on the mix's mesh, with AdamW and the mix's
    aggregator.  ``fault`` plants one of ``faults.FAULTS`` underneath."""
    from repro.core import AggregatorConfig
    from repro.core.compat import make_mesh
    from repro.data.synthetic import batch_pspecs
    from repro.launch.mesh import dp_axes_of
    from repro.models import build_model
    from repro.serve.step import sanitize_pspec
    from repro.train import Trainer, TrainerConfig, TrainStepConfig
    from jax.sharding import NamedSharding, PartitionSpec as P

    import faults

    mesh_cfg = cell.mix["mesh"]
    mesh = make_mesh((mesh_cfg["data"], mesh_cfg["model"]),
                     ("data", "model"), devices=devices[:cell.chips])
    model = build_model(cell.family.program_spec(cell.cfg))
    opt = _optimizer(cell.mix)
    model, opt = faults.plant(fault, model, opt)
    step_cfg = TrainStepConfig(
        aggregator=AggregatorConfig(**cell.mix["aggregator"]),
        clip_norm=cell.mix["clip_norm"], dp_axes=dp_axes_of(mesh))
    sds = jax.ShapeDtypeStruct((cell.global_batch, cell.seq), jnp.int32)
    example = {"tokens": sds, "labels": sds}
    trainer = Trainer(model, opt, mesh, lambda _: example,
                      TrainerConfig(step=step_cfg))
    sh = trainer.shardings

    def named(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, sanitize_pspec(s, mesh)), tree,
            is_leaf=lambda x: isinstance(x, P))

    step = faults.wrap_step(fault, trainer.step_fn)
    prog = Program(step, named(sh["params"]), named(sh["opt"]),
                   named(batch_pspecs(example, step_cfg.dp_axes)),
                   opt.init)
    prog.hlo_text = lambda *args: trainer.step_fn.lower(*args).compile() \
        .as_text()
    return prog


# ---------------------------------------------------------------------------
# set-up: the state, the pool, and the first (checked) steps
# ---------------------------------------------------------------------------

def _tree_norms(tree, names) -> jax.Array:
    flat = _flat(tree)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        flat[n].astype(jnp.float32)))) for n in names])


def _flat(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


@dataclasses.dataclass
class Readings:
    """What the first checked steps gave: each step's loss, each leaf's
    norm of the first (clipped) gradient and the gradient itself (host
    arrays by leaf name), each leaf's norm of the change after the
    checked steps."""
    losses: np.ndarray
    grad: np.ndarray
    grad_tree: dict
    delta: np.ndarray


class Makers:
    """The jitted programs of set-up for one program and cell, made once:
    ``state(words)`` -> (params, opt_state); ``pool(words)`` -> batches;
    ``first_grad(opt_state)`` -> leaf norms of the first gradient, from
    AdamW's first moment after one step; ``delta(params, words)`` ->
    leaf norms of the change from the seed's weights."""

    def __init__(self, prog: Program, cell: Cell):
        names = sorted(cell.family.leaf_shapes(cell.cfg))
        self.state = jax.jit(
            lambda w: (lambda p: (p, prog.opt_init(p)))(weights(w, cell)),
            out_shardings=(prog.param_sh, prog.opt_sh))
        n = cell.mix["pool_batches"]
        self.pool = jax.jit(lambda w: batches(w, cell, 0, n),
                            out_shardings=[prog.batch_sh] * n)
        b1 = cell.mix["optimizer"]["b1"]
        self.first_grad = jax.jit(
            lambda o: _tree_norms(o["m"], names) / (1 - b1))

        def change(p, w):
            key, flat = _key(w, 0), _flat(p)
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                flat[n] - cell.family.make_leaf(key, cell.cfg, n))))
                for n in names])
        self.delta = jax.jit(change)


def first_grad_tree(opt_state, cell: Cell) -> dict:
    """The first clipped gradient as AdamW holds it after one step,
    m / (1 - b1), as float32 host arrays by leaf name."""
    b1 = cell.mix["optimizer"]["b1"]
    return {n: np.asarray(v, np.float32) / np.float32(1 - b1)
            for n, v in _flat(jax.device_get(opt_state["m"])).items()}


def makers(prog: Program, cell: Cell) -> Makers:
    if prog.makers is None:
        prog.makers = Makers(prog, cell)
    return prog.makers


def set_up(prog: Program, cell: Cell, seed: int,
           phases: dict | None = None):
    """Weights, optimizer state and the batch pool from the seed, then
    the checked steps through ``prog.step``.  Returns (params, opt_state,
    pool, readings, steps done); ``phases`` gets the seconds of each
    part."""
    phases = {} if phases is None else phases
    t0 = time.perf_counter()
    mk = makers(prog, cell)
    words = seed_words(seed)
    params, opt_state = mk.state(words)
    pool = mk.pool(words)
    jax.block_until_ready((params, opt_state, pool))
    t1 = time.perf_counter()
    phases["state_and_pool"] = t1 - t0
    losses, grad, tree = [], None, None
    for i in range(cell.mix["checked_steps"]):
        params, opt_state, m = prog.step(params, opt_state, pool[i])
        losses.append(m["loss"])
        if i == 0:
            grad = mk.first_grad(opt_state)
            # to the host before the next step takes the state over
            tree = first_grad_tree(opt_state, cell)
    d = mk.delta(params, words)
    jax.block_until_ready((params, opt_state, d))
    phases["checked_steps"] = time.perf_counter() - t1
    readings = Readings(np.array([float(x) for x in losses]),
                        np.asarray(grad, np.float64), tree,
                        np.asarray(d, np.float64))
    return params, opt_state, pool, readings, cell.mix["checked_steps"]


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def run_steps(prog: Program, params, opt_state, pool, first: int,
              until: Callable[[int], bool]):
    """Dispatch steps over the pool, as a trainer does, keeping at most
    ``IN_FLIGHT`` steps queued behind the one just sent; stop once
    ``until(steps sent)`` holds, then wait for the last.  Returns
    (params, opt_state, losses)."""
    pending: collections.deque = collections.deque()
    losses = []
    n = 0
    while not until(n):
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("data"):
                batch = pool[(first + n) % len(pool)]
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt_state, m = prog.step(params, opt_state, batch)
            losses.append(m["loss"])
            pending.append(m["loss"])
            n += 1
            if len(pending) > IN_FLIGHT:
                with jax.profiler.TraceAnnotation("sync"):
                    pending.popleft().block_until_ready()
    with jax.profiler.TraceAnnotation("sync"):
        jax.block_until_ready((params, opt_state))
    return params, opt_state, losses


def timed_window(prog, params, opt_state, pool, first, seconds):
    t0 = time.perf_counter()
    params, opt_state, losses = run_steps(
        prog, params, opt_state, pool, first,
        lambda n: n > 0 and time.perf_counter() - t0 >= seconds)
    t1 = time.perf_counter()
    return params, opt_state, losses, {"t0": t0, "seconds": t1 - t0,
                                       "steps": len(losses)}


def traced_window(prog, params, opt_state, pool, first, trace_dir,
                  steps: int = TRACE_STEPS):
    jax.profiler.start_trace(trace_dir)
    try:
        params, opt_state, losses = run_steps(
            prog, params, opt_state, pool, first, lambda n: n >= steps)
    finally:
        jax.profiler.stop_trace()
    return params, opt_state, losses, {"steps": len(losses)}


def reduce_trace(trace_dir: str, hlo_text: str) -> dict:
    """The device trace under ``trace_dir``, read against the compiled
    step's HLO, which names the ops the trace records."""
    import trace_reduce as tr

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return tr.reduce_trace(paths[0], tr.read_hlo(hlo_text), HOST_LABELS)


def memory_peak(devices) -> int:
    """Peak bytes held on the fullest chip: buffers (``peak_bytes_in_use``:
    weights, optimizer state, batches) and the region a TPU reserves
    apart from them for the programs' temporaries
    (``peak_bytes_reserved``), which the buffers' count leaves out."""
    def held(stats):
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    return int(max(held(d.memory_stats() or {}) for d in devices))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def compare(prog: Readings, ref: dict, limits: dict) -> dict:
    """Each number that can be compared, with its limit (None where the
    cell's limits file holds no limit for it):

    loss_gap        worst checked step's |loss - reference| / reference;
    first_loss_gap  the same for the first step alone, which the
                    optimizer's later steps have not yet touched;
    grad_gap        worst leaf's gap between the norms of the first
                    clipped gradient, over the larger of that leaf's
                    reference norm and the median leaf's;
    grad_err        worst leaf's norm of the difference between the
                    first clipped gradients, over the same floor: unlike
                    a gap of norms it does not average rounding out;
    delta_gap       the same for the parameters' change over the checked
                    steps, leaving out leaves whose reference gradient is
                    nought to rounding.
    """
    def gap(got, want, keep=None):
        got, want = np.asarray(got), np.asarray(want)
        if keep is not None:
            got, want = got[keep], want[keep]
        floor = np.maximum(want, np.median(want))
        g = np.abs(got - want) / floor
        return float(np.max(g)) if np.all(np.isfinite(got)) else math.inf

    def diff(got, want, norms):
        def norm(x):
            x = x.ravel()
            return np.sqrt(float(np.dot(x, x)))
        d = np.array([norm(got[n] - want[n]) for n in sorted(want)])
        floor = np.maximum(norms, np.median(norms))
        return float(np.max(d / floor)) if np.all(np.isfinite(d)) \
            else math.inf

    loss = np.abs(prog.losses - ref["losses"]) / np.abs(ref["losses"])
    if not np.all(np.isfinite(loss)):
        loss = np.full_like(loss, math.inf)
    raw = ref["grad_raw"]
    keep = raw >= NOUGHT_GRAD_SHARE * np.median(raw)
    values = dict(zip(CHECKS, (
        float(np.max(loss)), float(loss[0]),
        gap(prog.grad, ref["grad"]),
        diff(prog.grad_tree, ref["grad_tree"], ref["grad"]),
        gap(prog.delta, ref["delta"], keep))))
    return {k: {"value": v, "limit": limits.get(k, {}).get("limit")}
            for k, v in values.items()}


def held(checks: dict) -> dict:
    """The numbers the cell compares: those its limits file gives a
    limit (``limits/<cell>.json``); the others are not compared."""
    return {k: c for k, c in checks.items() if c["limit"] is not None}


def passed(checks: dict) -> bool:
    """Some number is compared, and every one is within its limit."""
    kept = held(checks)
    return bool(kept) and all(c["value"] <= c["limit"]
                              for c in kept.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def read_metrics(cell: Cell, run: dict, which: list) -> dict:
    out = {}
    for m in which:
        reader = _load_module(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"),
                              "metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, devices, seed: int, seconds: float, trace: bool,
             t_start: float, prog: Program | None = None,
             log: CompileLog | None = None, fault: str = "") -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``prog`` replaces the program under test (the control does)."""
    import reference

    t_build = time.perf_counter()
    prog = prog or build_program(cell, devices, fault)
    phases = {"start": t_build - t_start,
              "build": time.perf_counter() - t_build}
    params, opt_state, pool, readings, done = set_up(prog, cell, seed,
                                                     phases)
    setup_s = time.perf_counter() - t_start
    compiles0 = log.compiles if log else 0
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            params, opt_state, losses, rec = traced_window(
                prog, params, opt_state, pool, done, tmp)
            rec["trace"] = reduce_trace(
                tmp, prog.hlo_text(params, opt_state, pool[0]))
        else:
            params, opt_state, losses, rec = timed_window(
                prog, params, opt_state, pool, done, seconds)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    window_compiles = (log.compiles - compiles0) if log else 0
    mem_peak = memory_peak(devices[:cell.chips])
    mem_stats = devices[0].memory_stats() or {}
    failed = sum(1 for x in losses if not math.isfinite(float(x)))
    host_pool = [jax.device_get(b) for b in pool[:cell.mix[
        "checked_steps"]]]
    del params, opt_state, pool, losses, prog
    t_ref = time.perf_counter()
    ref = reference.readings(cell, seed, devices[0], host_pool)
    ref_s = time.perf_counter() - t_ref
    checks = compare(readings, ref, cell.limits)
    run = {"cell": cell, "setup_s": setup_s, "record": rec,
           "chips": cell.chips,
           "peak": peak_flops(devices[0]) if trace else None,
           "flops_per_step": cell.flops_per_step,
           "tokens_per_step": cell.tokens_per_step}
    metrics = read_metrics(cell, run, cell.per_layer if trace
                           else cell.end_to_end)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": passed(checks) and failed == 0,
              "attempted": rec["steps"], "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = t["breakdown"]
    result["checks"] = held(checks)
    result["_window_compiles"] = window_compiles
    result["_reference_s"] = ref_s
    result["_setup_phases"] = phases
    result["_memory_stats"] = mem_stats
    return result


def peak_flops(device) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device.device_kind not in table:
        raise KeyError(f"device kind {device.device_kind!r} is not in "
                       f"peaks.json ({sorted(table)})")
    return float(table[device.device_kind]["bf16_flops_per_s"])


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        devices = require_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cache = configure_cache()
    log = CompileLog()
    result = run_cell(cell, devices, args.seed, args.seconds,
                      bool(args.trace), t_start, log=log)
    window_compiles = result.pop("_window_compiles")
    reference_s = result.pop("_reference_s")
    phases = result.pop("_setup_phases")
    stats = result.pop("_memory_stats")
    print(f"bench: compile cache {cache}: {log.lookups} lookups, "
          f"{log.hits} hits, {log.writes} writes; {log.compiles} backend "
          f"compiles, {log.seconds:.3f} s; {window_compiles} inside the "
          f"window; reference {reference_s:.3f} s", file=sys.stderr)
    print("bench: set-up seconds " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()), file=sys.stderr)
    print("bench: chip 0 memory after the window " + ", ".join(
        f"{k} {v}" for k, v in sorted(stats.items())), file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
