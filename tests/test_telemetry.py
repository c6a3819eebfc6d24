"""Telemetry subsystem (DESIGN.md §3.11): host spans, the metrics
registry, the measured-vs-predicted closure, and the named scopes that
carry the IR path into the compiled program.

The hard invariants pinned here:

* IR paths in the program — every ``bucket[i]`` and
  ``bucket[i].stage[j]`` path of the ReduceSchedule is a scope in the
  compiled HLO's ``op_name``s, and each stage's collective-permute bytes
  under its scope equal its ``wire_bytes`` (subprocess test on forced
  host devices);
* disabled-mode identity — with ``TelemetryConfig(enabled=False)`` the
  compiled HLO, metadata aside, and the schedule fingerprint are the
  same as a telemetry-on build's;
* every wall span is on the profiler's host plane.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import telemetry
from repro.core import schedule as schedule_mod
from repro.telemetry import closure, metrics as metrics_mod, trace


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Tests flip the process-global tracer; always restore 'off'."""
    yield
    telemetry.configure(trace.TelemetryConfig(enabled=False))
    telemetry.METRICS.reset()


# ---------------------------------------------------------------------------
# spans + trace schema
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_null_object():
    tracer = trace.Tracer(trace.TelemetryConfig(enabled=False))
    s1 = tracer.span("a", cat="wall", ir_path="bucket[0]")
    s2 = tracer.span("b")
    assert s1 is s2 is trace._NULL_SPAN
    with s1 as sp:
        sp.set("k", 1)          # no-op, no error
    assert tracer.roots == []


def test_unknown_category_rejected_only_when_enabled():
    tracer = trace.Tracer(trace.TelemetryConfig(enabled=True))
    with pytest.raises(ValueError):
        tracer.span("x", cat="trace")
    off = trace.Tracer(trace.TelemetryConfig(enabled=False))
    assert off.span("x", cat="trace") is trace._NULL_SPAN


def test_span_nesting_ordering_and_roundtrip():
    tracer = trace.Tracer(trace.TelemetryConfig(enabled=True))
    with tracer.span("step", cat="wall") as outer:
        with tracer.span("bucket", ir_path="bucket[0]"):
            with tracer.span("stage", ir_path="bucket[0].stage[0]",
                             wire_bytes=128):
                pass
        with tracer.span("bucket", ir_path="bucket[1]"):
            pass
    assert len(tracer.roots) == 1
    assert [c.attrs["ir_path"] for c in outer.children] == \
        ["bucket[0]", "bucket[1]"]
    # children lie within the parent interval and are time-ordered
    for parent in tracer.iter_spans():
        assert parent.t1 >= parent.t0
        prev_end = parent.t0
        for c in parent.children:
            assert c.t0 >= prev_end - 1e-9
            assert c.t1 <= parent.t1 + 1e-9
            prev_end = c.t0
    # JSON round-trip preserves the forest exactly
    rec = tracer.to_json()
    assert rec["schema"] == trace.TRACE_SCHEMA
    back = trace.from_json(json.loads(json.dumps(rec)))
    assert [s.to_json() for s in back] == rec["spans"]
    assert back[0].children[0].children[0].attrs["wire_bytes"] == 128
    with pytest.raises(ValueError):
        trace.from_json({"schema": "repro/other/v9"})


def test_exception_unwind_closes_dangling_spans():
    tracer = trace.Tracer(trace.TelemetryConfig(enabled=True))
    with pytest.raises(RuntimeError):
        with tracer.span("outer"):
            ctx = tracer.span("inner", cat="wall")
            ctx.__enter__()           # never exited explicitly
            raise RuntimeError("boom")
    outer = tracer.roots[0]
    inner = outer.children[0]
    assert inner.t1 >= inner.t0 > 0
    assert tracer._stack == []


def test_chrome_trace_is_perfetto_shaped(tmp_path):
    tracer = trace.Tracer(trace.TelemetryConfig(enabled=True))
    with tracer.span("outer", cat="wall"):
        with tracer.span("inner", cat="wall", ir_path="bucket[0]"):
            pass
    path = tmp_path / "trace.json"
    tracer.write(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["cat"] in trace.CATEGORIES
    assert {ev["tid"] for ev in evs} == {0}      # one host track
    assert doc["repro"]["schema"] == trace.TRACE_SCHEMA
    assert trace.from_json(doc["repro"])         # reloads as spans


def test_wall_span_is_on_the_profiler_host_plane(tmp_path):
    """A wall span also enters a ``jax.profiler.TraceAnnotation``: under
    the profiler it is an event of the host plane, on the device
    trace's clock, nested as the spans are."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tracer = telemetry.configure(trace.TelemetryConfig(enabled=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("unit.outer", cat="wall"):
            with tracer.span("unit.inner", cat="wall"):
                jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    events = {e.name: e for line in host.lines for e in line.events
              if e.name.startswith("unit.")}
    assert set(events) == {"unit.outer", "unit.inner"}
    outer, inner = events["unit.outer"], events["unit.inner"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= outer.end_ns
    assert [s.name for s in tracer.iter_spans()] == \
        ["unit.outer", "unit.inner"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = metrics_mod.MetricsRegistry()
    c = reg.counter("bytes", help="b")
    c.inc(10, algo="ring")
    c.inc(5, algo="ring")
    c.inc(1, algo="rhd")
    assert c.get(algo="ring") == 15 and c.get(algo="rhd") == 1
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("height")
    g.set(3.5)
    g.set(4.5)
    assert g.get() == 4.5
    h = reg.histogram("lat")
    for v in range(100):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50, abs=1)
    assert h.percentile(99) == pytest.approx(98, abs=1)
    snap = reg.snapshot()
    assert snap["schema"] == metrics_mod.METRICS_SCHEMA
    assert snap["metrics"]["lat"]["values"][""]["count"] == 100
    text = reg.render()
    assert "bytes [counter]" in text and "algo=ring" in text


def test_kind_conflict_raises():
    reg = metrics_mod.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_reservoir_bounded():
    reg = metrics_mod.MetricsRegistry()
    h = reg.histogram("big")
    for v in range(metrics_mod.MAX_SAMPLES + 100):
        h.observe(float(v))
    vals = h.samples[metrics_mod.label_key({})]
    assert len(vals) == metrics_mod.MAX_SAMPLES
    assert vals[0] == 100.0          # FIFO: oldest dropped


def test_record_schedule_counts_wire_bytes_by_algorithm():
    reg = metrics_mod.MetricsRegistry()
    sched = schedule_mod.synthetic([1 << 20, 1 << 20], "ring_rsa",
                                   axis_sizes=(8,))
    metrics_mod.record_schedule(sched, registry=reg)
    want = sum(st.wire_bytes for _p, _b, st in sched.iter_stages())
    c = reg.counter("schedule_wire_bytes")
    assert c.get(algorithm="ring_rsa", codec="none") == want
    assert reg.counter("schedule_stages").get(
        algorithm="ring_rsa", codec="none") == 2


# ---------------------------------------------------------------------------
# closure: calibration + residual band
# ---------------------------------------------------------------------------

def test_calibrate_exact_on_proportional_pairs():
    pairs = [(1.0, 250.0), (2.0, 500.0), (4.0, 1000.0)]
    assert closure.calibrate(pairs) == pytest.approx(250.0)
    assert closure.calibrate([]) == 0.0


def _fake_measured(sched, k_by_p):
    return {path: k_by_p[int(st.axis_size)] * st.predicted_s
            for path, _b, st in sched.iter_stages()}


def test_closure_report_proportional_measurements_in_band():
    sched = schedule_mod.synthetic([1 << 20, 4 << 20, 16 << 20],
                                   "ring_rsa", axis_sizes=(8,))
    rep = closure.closure_report(sched, _fake_measured(sched, {8: 300.0}))
    assert rep["n_stages"] == 3 and rep["n_gated"] == 3
    assert rep["calibration"]["k"] == pytest.approx(300.0)
    assert rep["max_ratio"] == pytest.approx(1.0)
    assert rep["all_within_band"] is True


def test_closure_report_per_axis_size_calibration():
    """A composed schedule whose two participant counts have wildly
    different host constants must still close: calibration is fitted
    per axis_size (DESIGN.md §3.11), so only SIZE-scaling errors within
    one participant count can trip the band."""
    strategy = f"ring_rsa{schedule_mod.SEP}rhd_rsa"
    sched = schedule_mod.synthetic([4 << 20, 16 << 20], strategy,
                                   axis_sizes=(2, 4),
                                   axis_names=("pod", "data"))
    rep = closure.closure_report(
        sched, _fake_measured(sched, {2: 20.0, 4: 900.0}))
    assert rep["all_within_band"] is True
    per = rep["calibration"]["per_axis_size"]
    assert per["2"]["k"] == pytest.approx(20.0)
    assert per["4"]["k"] == pytest.approx(900.0)


def test_closure_report_out_of_band_detected():
    sched = schedule_mod.synthetic([1 << 20, 4 << 20, 16 << 20],
                                   "ring_rsa", axis_sizes=(8,))
    measured = _fake_measured(sched, {8: 300.0})
    worst = max(measured)            # break one stage's size scaling
    measured[worst] *= closure.BAND_FACTOR * 40
    rep = closure.closure_report(sched, measured)
    assert rep["all_within_band"] is False
    assert rep["max_ratio"] > closure.BAND_FACTOR


def test_closure_report_small_stages_reported_not_gated():
    sched = schedule_mod.synthetic([1024], "ring_rsa", axis_sizes=(8,))
    measured = _fake_measured(sched, {8: 1e9})   # absurd, but ungated
    rep = closure.closure_report(sched, measured)
    assert rep["n_stages"] == 1 and rep["n_gated"] == 0
    assert rep["all_within_band"] is True        # vacuous by design
    assert rep["stages"][0]["gated"] is False


def test_closure_report_huge_stages_outside_regime_not_gated():
    """Above MAX_BAND_BYTES the host backend's effective bandwidth
    degrades with buffer size (cache/NUMA curvature), so a 512-proc
    dryrun's 100MB+ buckets must not trip the band that the 1-16MB
    artifact cells calibrate; they are reported, in-regime stages
    still gate."""
    sched = schedule_mod.synthetic([1 << 20, 256 << 20], "ring_rsa",
                                   axis_sizes=(8,))
    measured = _fake_measured(sched, {8: 300.0})
    big = max(sched.iter_stages(),
              key=lambda t: t[2].wire_bytes)[0]
    measured[big] *= closure.BAND_FACTOR * 40    # way off, but ungated
    rep = closure.closure_report(sched, measured)
    by_path = {r["path"]: r for r in rep["stages"]}
    assert by_path[big]["wire_bytes"] > closure.MAX_BAND_BYTES
    assert by_path[big]["gated"] is False
    assert rep["n_gated"] == 1                   # only the 1MB stage
    assert rep["all_within_band"] is True
    # the fit never saw the out-of-regime stage
    assert rep["calibration"]["k"] == pytest.approx(300.0)


def test_closure_report_missing_measurement_raises():
    sched = schedule_mod.synthetic([1 << 20], "ring_rsa", axis_sizes=(8,))
    with pytest.raises(KeyError):
        closure.closure_report(sched, {})


def test_measured_timeline_matches_predicted_when_proportional():
    sched = schedule_mod.synthetic([1 << 20, 4 << 20], "ring_rsa",
                                   axis_sizes=(8,))
    from repro.core import overlap
    compute_s = 50 * sched.predicted_s
    measured = _fake_measured(sched, {8: 123.0})
    tl = closure.measured_timeline(sched, measured, 123.0, compute_s)
    ref = overlap.simulate_schedule(sched, compute_s=compute_s)
    assert tl.step_s == pytest.approx(ref.step_s, rel=1e-9)
    assert tl.overlap_fraction == pytest.approx(ref.overlap_fraction,
                                                rel=1e-9)
    with pytest.raises(ValueError):
        closure.measured_timeline(sched, measured, 0.0, compute_s)


# ---------------------------------------------------------------------------
# the committed artifact
# ---------------------------------------------------------------------------

def test_committed_artifact_is_current():
    """BENCH_telemetry.json validates against the CURRENT cost model
    without re-measuring (the same gate the regen CI job runs)."""
    assert closure.check_artifact() == []


def test_check_artifact_flags_drift(tmp_path):
    with open(closure.TELEMETRY_ARTIFACT) as f:
        art = json.load(f)
    # (a) wrong schema
    bad = dict(art, schema="repro/telemetry/v0")
    p = tmp_path / "a.json"
    p.write_text(json.dumps(bad))
    assert any("schema" in s for s in closure.check_artifact(str(p)))
    # (b) a stored predicted_s that no longer matches the model
    bad = json.loads(json.dumps(art))
    bad["cells"][0]["stages"][0]["predicted_s"] *= 1.5
    p = tmp_path / "b.json"
    p.write_text(json.dumps(bad))
    assert any("cost model drifted" in s
               for s in closure.check_artifact(str(p)))
    # (c) missing file
    assert any("missing" in s
               for s in closure.check_artifact(str(tmp_path / "no.json")))


def test_artifact_cells_cover_ops_and_codec():
    cells = closure.artifact_cells()
    assert {c["name"] for c in cells} == \
        {"ring_rsa@8", "rhd_rsa@8", "ring_rsa+int8@8", "ring×rhd@2x4"}
    assert any(c["codec"] != "none" for c in cells)
    ops = set()
    for c in cells:
        for _p, _b, st in closure.cell_schedule(c).iter_stages():
            ops.add(st.op)
    assert {"allreduce", "reduce_scatter", "all_gather"} <= ops


# ---------------------------------------------------------------------------
# IR-path resolution + disabled-mode identity (forced multi-device)
# ---------------------------------------------------------------------------

_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.pop("REPRO_TRACE", None)
import re, sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import telemetry
from repro.core import AggregatorConfig, GradientAggregator, PlanCache
from repro.core.compat import shard_map
from repro.launch import hlo_analysis as H
from repro.telemetry import trace

p = 4
mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
D = 16

def loss(params, x):
    h = x
    for k in sorted(params):
        h = jnp.tanh(h @ params[k])
    return jnp.sum(h * h)

params = {f"w{i}": jax.random.normal(jax.random.PRNGKey(i), (D, D)) * 0.3
          for i in range(3)}
x = jax.random.normal(jax.random.PRNGKey(9), (p * 2, D))

def build():
    agg = GradientAggregator(
        AggregatorConfig(strategy="rhd_rsa", fusion_threshold_mb=0.0005),
        ("data",), cache=PlanCache())
    def local(params, x):
        g = jax.grad(loss)(params, x)
        return agg(g)
    fn = jax.jit(shard_map(local, mesh, in_specs=(P(), P("data")),
                           out_specs=P(), axis_names={"data"},
                           check_vma=False))
    return fn, agg

# -- pass 1: telemetry OFF (the default) ------------------------------------
fn_off, agg_off = build()
hlo_off = fn_off.lower(params, x).compile().as_text()
fp_off = agg_off.last_schedule.fingerprint()

# -- pass 2: telemetry ON ---------------------------------------------------
telemetry.configure(trace.TelemetryConfig(enabled=True))
fn_on, agg_on = build()
hlo_on = fn_on.lower(params, x).compile().as_text()
sched = agg_on.last_schedule

# disabled-mode identity: the program is the same, metadata aside
assert H.strip_metadata(hlo_on) == H.strip_metadata(hlo_off), \
    "telemetry changed the compiled program"
assert sched.fingerprint() == fp_off, "telemetry changed the fingerprint"

# every IR bucket/stage path is a scope of the compiled program, under
# the aggregator's scope, and each stage's permutes carry its hop scopes
names = ["/" + H.scope_path(n) + "/"
         for n in re.findall(r'op_name="([^"]*)"', hlo_on)]

def scoped(scope):
    return [n for n in names if "/aggregate/" + scope + "/" in n]

for bucket in sched.buckets:
    assert scoped(bucket.path), bucket.path
stage_sum = 0
for path, bucket, st in sched.iter_stages():
    scope = path.replace(".", "/")          # bucket[i]/stage[j]
    assert scoped(scope), path
    assert scoped(scope + "/hop[0]"), path
    got = H.scope_collective_bytes(hlo_on, "aggregate/" + scope)
    if st.hlo_kind == "collective-permute":
        # the stage's own permutes, counted in the program, are its
        # scheduled wire bytes
        assert got == st.wire_bytes, (path, got, st.wire_bytes)
    stage_sum += got

# and together they are every permute the program holds
charged = H.analyze(hlo_on).collective_bytes.get("collective-permute", 0)
assert stage_sum == charged, (stage_sum, charged)
print("OK", stage_sum, "==", charged)
"""


@pytest.mark.timeout(600)
def test_ir_paths_and_disabled_mode_identity_multidev():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET % os.path.abspath(src)],
        capture_output=True, text=True, timeout=580, env=env)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    assert "OK" in proc.stdout
