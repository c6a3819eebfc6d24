"""Device time by the program's named scopes (``scopes.py``) and the
readers that report it, on synthetic ops, on the CPU-compiled step and
on traces recorded on v5e chips."""
import gzip
import os
import shutil

import jax
import pytest

import bench
import record_trace
import scopes
import tiny
import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(tr.__file__), "testdata")
NEW = ("fwd_ms", "bwd_ms", "recompute_ms", "optimizer_ms", "attn_core_ms",
       "agg_compute_ms")
EXISTING = ("mfu_pct", "matmul_roofline", "device_idle_pct", "agg_ms",
            "agg_exposed_ms")

# Paths as XLA keeps them in a compiled step's op names.
PATHS = {
    "jit(step)/jvp(forward)/while/body/closed_call/attention/sdpa/"
    "dot_general": ("forward", True),
    "jit(step)/transpose(jvp(forward))/while/body/checkpoint/attention/"
    "sdpa/dot_general": ("backward", True),
    "jit(step)/transpose(jvp())/while/body/checkpoint/"
    "rematted_computation/mlp/dot_general": ("recompute", False),
    "jit(step)/transpose(jvp(sdpa))/cos": ("backward", True),
    "jit(step)/shard_map/transpose(jvp(aggregate))/bucket[1]/stage[0]/"
    "hop[2]/add": ("aggregation", False),
    "jit(step)/shard_map/aggregate/bucket[3]/stage[0]/mul":
        ("aggregation", False),
    "clip/psum": ("optimizer", False),
    "jit(step)/optimizer/sub": ("optimizer", False),
    "jit(step)/shard_map/div": ("unscoped", False),
    "params['embed']": ("unscoped", False),
    "": ("unscoped", False),
    # a name that only holds a scope's name is not that scope, nor is a
    # jitted function's name
    "jit(step)/jvp()/sdpa_full/aggregated/mul": ("forward", False),
    "jit(step)/jvp()/jit(clip)/min": ("forward", False),
    "jit(step)/aggregate/bucket[0]/stage[0]/hop[1]/jit(clip)/max":
        ("aggregation", False),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phase_and_attention_core_of_a_path(path):
    want, core = PATHS[path]
    assert scopes.phase(path) == want
    assert (scopes.ATTN_CORE in scopes.names(path)) == core


def test_names_take_the_wrappers_off():
    assert scopes.names("jit(step)/transpose(jvp(sdpa))/cos") == \
        {"jit(step)", "sdpa", "cos"}
    assert scopes.names("vmap(norm)/jit(clip)/max") == \
        {"norm", "jit(clip)", "max"}
    assert "aggregate" in scopes.names(
        "a/transpose(jvp(aggregate))/bucket[0]/stage[1]/hop[0]/add")


def test_reduction_on_synthetic_ops():
    """Phases partition the non-collective ops' time, averaged over the
    devices; collectives count to none of them; the attention core and
    the model scopes are read in every phase."""
    labels = {
        "fusion.1": "jit(s)/jvp()/attention/sdpa/dot_general",
        "fusion.2": "jit(s)/transpose(jvp())/checkpoint/"
                    "rematted_computation/attention/sdpa/exp",
        "fusion.3": "jit(s)/transpose(jvp())/checkpoint/mlp/dot_general",
        "fusion.4": "jit(s)/transpose(jvp(aggregate))/bucket[0]/mul",
        "cp-start.5": "jit(s)/aggregate/bucket[0]/stage[0]/hop[0]/"
                      "ppermute",
        "fusion.6": "jit(s)/optimizer/sub",
        "copy.7": "",
    }
    kinds = {n: "collective" if n.startswith("cp") else "other"
             for n in labels}
    hlo = tr.Hlo(kinds, {}, labels, {})
    secs = {"fusion.1": 1.0, "fusion.2": 2.0, "fusion.3": 4.0,
            "fusion.4": 8.0, "cp-start.5": 16.0, "fusion.6": 32.0,
            "copy.7": 64.0}
    trace = {"per_device": [{"per_op_s": secs},
                            {"per_op_s": {k: 3 * v
                                          for k, v in secs.items()}}]}
    r = scopes.reduce(trace, hlo)
    assert r["phases"] == {"forward": 2.0, "backward": 8.0,
                           "recompute": 4.0, "optimizer": 64.0,
                           "aggregation": 16.0, "unscoped": 128.0}
    assert r["compute_s"] == 2 * (1 + 2 + 4 + 8 + 32 + 64)
    assert r["attn_core_s"] == 2 * (1 + 2)
    assert r["model_s"]["attention"] == 6.0
    assert r["model_s"]["mlp"] == 8.0
    assert r["present"] == ["aggregate", "attention", "mlp", "optimizer",
                            "sdpa"]


def _recorded(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("scopes") / (name + ".xplane.pb")
    with gzip.open(os.path.join(TESTDATA, name + ".xplane.pb.gz"),
                   "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with gzip.open(os.path.join(TESTDATA, name + ".hlo.txt.gz"),
                   "rt") as f:
        text = f.read()
    return str(path), text


def _run(path, text, chips=4):
    """The run a reader gets from the harness's traced run of the
    recording's tiny cell, with the scope record made as ``of_run``
    makes it."""
    hlo = tr.read_hlo(text)
    t = tr.reduce_trace(path, hlo, bench.HOST_LABELS)
    t["scopes"] = scopes.reduce(t, hlo)
    cell = record_trace.tiny_cell(chips)
    return {"cell": cell, "record": {"steps": 2, "trace": t},
            "chips": chips, "peak": 197e12, "setup_s": 0.0,
            "flops_per_step": cell.flops_per_step,
            "tokens_per_step": cell.tokens_per_step}


def _read(run, names):
    out = {}
    for m in names:
        reader = bench._load_module(
            os.path.join(tiny.CHIP, "metrics", m + ".py"), "metric_" + m)
        out[m] = reader.read(run)
    return out


def test_dp4_tiny_readings_unchanged(tmp_path_factory):
    """The trace recorded before the program had scopes: every reading
    the benchmark had reads as it did; the phases JAX marks itself are
    read, and the metrics of scopes the program did not carry then have
    nothing to read, as in a run of a program without them."""
    run = _run(*_recorded("dp4_tiny", tmp_path_factory))
    got = _read(run, EXISTING + NEW)
    assert {m: got[m] for m in EXISTING} == {
        "mfu_pct": pytest.approx(1.524940160531742, rel=1e-12),
        "matmul_roofline": pytest.approx(35.77212105692071, rel=1e-12),
        "device_idle_pct": pytest.approx(0.2701112966809305, rel=1e-12),
        "agg_ms": pytest.approx(11.497997875000001, rel=1e-12),
        "agg_exposed_ms": pytest.approx(9.426202750000002, rel=1e-12)}
    assert got["fwd_ms"] == pytest.approx(0.194269, rel=1e-9)
    assert got["bwd_ms"] == pytest.approx(0.865591375, rel=1e-9)
    assert got["recompute_ms"] == pytest.approx(0.030788625, rel=1e-9)
    assert got["optimizer_ms"] is None
    assert got["attn_core_ms"] is None
    assert got["agg_compute_ms"] is None


def test_readers_read_nothing_without_a_trace():
    run = {"record": {"steps": 3}}
    assert all(v is None for v in _read(run, NEW).values())


def test_step_hlo_is_the_harness_step():
    """``of_run`` reads the op names of the step again from a compile for
    the state's shapes: the same instructions, kinds and op names as the
    harness's compile of the step it drove (the source locations of the
    two compiles' callers differ)."""
    from repro.launch.hlo_analysis import strip_metadata

    cell = tiny.cell()
    prog = bench.build_program(cell, jax.devices())
    params, opt, pool, _, _ = bench.set_up(prog, cell, 11)
    again = scopes.step_hlo(cell)
    ran = prog.hlo_text(params, opt, pool[0])
    assert tr.read_hlo(again) == tr.read_hlo(ran)
    assert strip_metadata(again) == strip_metadata(ran)


def test_dp4_scoped_trace(tmp_path_factory):
    """The trace recorded on four v5e chips from the scoped program (two
    steps of the tiny dp4 cell): every scope is in the program, every
    metric reads, the phases partition the compute time, which is the
    union of the compute ops' intervals, and the aggregator's
    collectives are all under its scope but the metric means'."""
    path, text = _recorded("dp4_scoped", tmp_path_factory)
    run = _run(path, text)
    s = run["record"]["trace"]["scopes"]
    assert s["present"] == sorted(scopes.SCOPES)
    got = _read(run, NEW)
    assert all(got[m] > 0 for m in NEW), got
    unscoped = 1e3 * s["phases"]["unscoped"] / 2
    assert sum(got[m] for m in NEW if m != "attn_core_ms") + unscoped == \
        pytest.approx(1e3 * s["compute_s"] / 2, rel=1e-9)
    assert got["attn_core_ms"] < got["fwd_ms"] + got["recompute_ms"] \
        + got["bwd_ms"]
    hlo = tr.read_hlo(text)
    devices, _ = tr.load(path)
    union = sum(tr.length(tr.union(
        [(b, e) for n, b, e in d.ops if hlo.kinds[n] in ("matmul", "other")]))
        for d in devices) * 1e-9 / len(devices)
    assert s["compute_s"] == pytest.approx(union, rel=1e-2)
    outside = {hlo.labels[n] for d in devices for n, _, _ in d.ops
               if hlo.kinds[n] == "collective"
               and scopes.AGGREGATE not in scopes.names(hlo.labels[n])}
    assert outside <= {"jit(local_step)/shard_map/psum"}, outside
