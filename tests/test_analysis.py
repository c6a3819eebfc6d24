"""The static-analysis wall (repro.analysis, DESIGN.md §3.9).

Three fronts:

* **fixture wall** — one deliberately-broken ReduceSchedule per
  verifier error rule (byte mismatch, bad stage pairing, gapped/
  overlapping leaf partition, non-monotone readiness, straddled
  crossover, underivable wire tolerance, latency-sensitive
  fingerprint), each asserting the RIGHT ``rule_id`` fires;
* **clean sweep** — every schedule the planner/matrix currently
  produces (all designs × p ∈ {1..128} ∪ {512}, composed two-level,
  three-axis) verifies with zero diagnostics, as do attached planner
  schedules (fixed, auto-selector, overlap);
* **linter walls** — hlo_lint rules on synthetic HLO (wire_check
  equivalence with the roofline wrapper, interleave, mixed-dtype,
  unexpected-allreduce + baseline), compat_lint on violation fixtures
  and on the real source tree, and the CLI's exit-code contract
  (non-zero on a mutated schedule JSON, zero on a clean one).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import ERROR, WARN, Diagnostic, compat_lint, hlo_lint
from repro.analysis import verify as av
from repro.core import schedule as sm
from repro.experiments import matrix

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def rule_ids(sched):
    return sorted({d.rule_id for d in av.verify_schedule(sched)})


def flat(n_buckets=2, p=8):
    return sm.synthetic([(8 << 20) // (i + 1) for i in range(n_buckets)],
                        "rhd_rsa", (p,), ("data",))


def attached(threshold=16 << 10, switch_points=(), selector=None):
    import jax
    import jax.numpy as jnp
    tree = {"a": jax.ShapeDtypeStruct((1000,), jnp.float32),
            "b": jax.ShapeDtypeStruct((2000,), jnp.float32),
            "c": jax.ShapeDtypeStruct((3000,), jnp.float32),
            "d": jax.ShapeDtypeStruct((50000,), jnp.float32)}
    return sm.plan(tree, axis_names=("data",), axis_sizes=(8,),
                   threshold_bytes=threshold, selector=selector)


def replace_bucket(sched, i, **kw):
    buckets = list(sched.buckets)
    buckets[i] = dataclasses.replace(buckets[i], **kw)
    return dataclasses.replace(sched, buckets=tuple(buckets))


# ---------------------------------------------------------------------------
# fixture wall: each error rule fires with the right rule_id
# ---------------------------------------------------------------------------

def test_clean_schedules_have_no_diagnostics():
    assert rule_ids(flat()) == []
    assert rule_ids(attached()) == []
    comp = sm.synthetic([4 << 20], "ring_rsa×rhd_rsa", (2, 8),
                        ("pod", "data"))
    assert rule_ids(comp) == []


def test_sv000_bad_placement_and_duplicate_axes():
    s = flat()
    assert "SV000" in rule_ids(dataclasses.replace(s, placement="eager"))
    assert "SV000" in rule_ids(dataclasses.replace(
        s, axis_names=("data", "data"), axis_sizes=(4, 2)))


def test_sv001_stage_byte_mismatch():
    s = flat()
    b = s.buckets[0]
    bad_stage = dataclasses.replace(b.stages[0],
                                    wire_bytes=b.stages[0].wire_bytes + 64)
    bad = replace_bucket(s, 0, stages=(bad_stage,))
    diags = av.verify_schedule(bad)
    hits = [d for d in diags if d.rule_id == "SV001"]
    assert hits, diags
    # anchored at the corrupted stage's IR path
    assert any(d.location == "bucket[0].stage[0]" for d in hits)
    assert all(d.severity == ERROR for d in hits)


def test_sv001_wrong_bucket_total():
    # swapping a bucket's strategy name without re-deriving its stages
    # breaks both the structural match and the closed form
    s = flat()
    bad = replace_bucket(s, 0, strategy="ring_rsa")
    assert "SV001" in rule_ids(bad)


def test_sv002_bad_stage_pairing():
    comp = sm.synthetic([8 << 20], "ring_rsa×rhd_rsa", (2, 8),
                        ("pod", "data"))
    b = comp.buckets[0]
    assert [st.op for st in b.stages] == \
        ["reduce_scatter", "allreduce", "all_gather"]
    # drop the all_gather: the reduce_scatter never terminates
    bad = replace_bucket(comp, 0, stages=b.stages[:-1])
    assert "SV002" in rule_ids(bad)
    # reorder: gather before its scatter
    bad = replace_bucket(comp, 0,
                         stages=(b.stages[2], b.stages[1], b.stages[0]))
    assert "SV002" in rule_ids(bad)


def test_sv002_axis_covered_twice():
    s = flat(n_buckets=1)
    b = s.buckets[0]
    bad = replace_bucket(s, 0, stages=b.stages + b.stages)
    assert "SV002" in rule_ids(bad)


def test_sv003_gapped_leaf_partition():
    s = attached()
    b = s.buckets[0]
    assert len(b.leaf_indices) > 1
    bad = replace_bucket(s, 0, leaf_indices=b.leaf_indices[:-1])
    assert "SV003" in rule_ids(bad)


def test_sv003_overlapping_leaves():
    s = attached()
    b0, b1 = s.buckets[0], s.buckets[1]
    bad = replace_bucket(s, 1,
                         leaf_indices=b1.leaf_indices + b0.leaf_indices[:1])
    assert "SV003" in rule_ids(bad)


def test_sv004_ranks_not_a_permutation():
    s = flat(n_buckets=2)
    bad = replace_bucket(replace_bucket(s, 0, readiness_rank=0), 1,
                         readiness_rank=0)
    assert "SV004" in rule_ids(bad)


def test_sv004_non_monotone_readiness():
    s = attached()
    assert len(s.buckets) >= 2
    r0 = s.buckets[0].readiness_rank
    r1 = s.buckets[1].readiness_rank
    bad = replace_bucket(replace_bucket(s, 0, readiness_rank=r1), 1,
                         readiness_rank=r0)
    assert "SV004" in rule_ids(bad)


def test_sv005_straddled_crossover():
    s = attached()
    fused = [b for b in s.buckets if len(b.leaf_indices) > 1]
    assert fused, "fixture needs a multi-leaf bucket"
    # plant a switch point strictly inside the first fused bucket
    first_leaf_bytes = s.plan.leaves[fused[0].leaf_indices[0]].size * 4
    bad = dataclasses.replace(s, switch_points=(first_leaf_bytes + 1,))
    assert "SV005" in rule_ids(bad)
    # aligned planner layouts never straddle their own switch points
    from repro.core import selector as selector_mod
    auto = attached(selector=selector_mod.AnalyticSelector())
    assert rule_ids(auto) == []


def test_sv006_underivable_wire_tolerance():
    bad = dataclasses.replace(flat(), wire_dtype="int8")
    assert "SV006" in rule_ids(bad)
    assert av.wire_tolerance(bad) is None
    ok = dataclasses.replace(flat(), wire_dtype="bfloat16")
    # (log2 8 + 1) * 2^-8 — the bound test_wire_dtype.py validates
    assert av.wire_tolerance(ok) == pytest.approx(4 * 2 ** -8)
    assert "SV006" not in rule_ids(ok)


def test_sv007_latency_sensitive_fingerprint():
    @dataclasses.dataclass(frozen=True)
    class LatencyLeaky(sm.ReduceSchedule):
        def fingerprint(self, detached=False):
            import hashlib
            blob = (super().fingerprint(detached)
                    + repr(self.predicted_s)).encode()
            return hashlib.sha256(blob).hexdigest()[:16]

    base = flat()
    leaky = LatencyLeaky(**{f.name: getattr(base, f.name)
                            for f in dataclasses.fields(base)})
    assert "SV007" in rule_ids(leaky)
    assert rule_ids(base) == []


def coded(strategy="ring_rsa", codec="int8", p=8):
    return sm.synthetic([8 << 20], strategy, (p,), ("data",), codec=codec)


def replace_stage(sched, **kw):
    b = sched.buckets[0]
    stages = (dataclasses.replace(b.stages[0], **kw),) + b.stages[1:]
    return replace_bucket(sched, 0, stages=stages)


def test_sv008_unknown_codec_has_no_bound():
    """A codec the wire-identity table doesn't know cannot get a derived
    error bound — the verifier must refuse it rather than pass it as
    uncoded, and codec_tolerance (what the numerics walls divide by)
    must refuse to produce a number."""
    bad = replace_stage(coded(), codec="int4")
    assert rule_ids(bad) == ["SV008"]
    hits = [d for d in av.verify_schedule(bad) if d.rule_id == "SV008"]
    assert hits[0].location == "bucket[0].stage[0]"
    assert hits[0].severity == ERROR
    assert "no derivable per-hop error bound" in hits[0].message
    assert av.codec_tolerance(bad) is None


def test_sv008_coded_wire_bytes_mismatch():
    """Corrupting a codec'd stage's wire_bytes trips the SV008 encoded
    re-derivation — and ONLY SV008: SV001 defers coded buckets to the
    codec rule, so the mismatch can't double-report or slip through."""
    s = coded()
    bad = replace_stage(s, wire_bytes=s.buckets[0].stages[0].wire_bytes + 64)
    assert rule_ids(bad) == ["SV008"]
    hits = [d for d in av.verify_schedule(bad) if d.rule_id == "SV008"]
    assert "on the wire" in hits[0].message


def test_sv008_codec_on_non_permute_algorithm():
    """Vendor psum exposes no per-hop ppermute to re-quantize at — a
    codec'd psum stage is unexecutable and must be rejected statically
    (the planner refuses to build one; the verifier catches hand-edited
    or deserialized IR)."""
    bad = replace_stage(sm.synthetic([8 << 20], "psum", (8,), ("data",)),
                        codec="int8")
    assert rule_ids(bad) == ["SV008"]
    hits = [d for d in av.verify_schedule(bad) if d.rule_id == "SV008"]
    assert "ppermute" in hits[0].message


def test_sv008_clean_coded_schedules_and_summary_tolerance():
    """Every registered codec verifies clean on both ppermute
    algorithms, the composed per-level mix verifies clean, and
    verify_summary carries the derived codec_tolerance the multidev
    wall asserts against (None/0 would make that wall vacuous)."""
    for spec in ("bf16", "int8", "fp8_e4m3"):
        for strat in ("ring_rsa", "rhd_rsa"):
            s = coded(strategy=strat, codec=spec)
            assert rule_ids(s) == [], (strat, spec)
            tol = av.codec_tolerance(s)
            assert tol is not None and tol > 0, (strat, spec)
    comp = sm.synthetic([4 << 20], "ring_rsa×rhd_rsa", (4, 8),
                        ("pod", "data"), codec="int8×bf16")
    assert rule_ids(comp) == []
    rec = av.verify_summary(coded(), context="unit")
    assert rec["codec_tolerance"] == pytest.approx(
        av.codec_tolerance(coded()))
    assert rec["n_errors"] == 0
    json.dumps(rec)
    # uncoded schedules report codec_tolerance 0.0, never None
    assert av.verify_summary(flat())["codec_tolerance"] == 0.0


# ---------------------------------------------------------------------------
# clean sweep: everything the planner/matrix produces verifies
# ---------------------------------------------------------------------------

def test_every_matrix_cell_verifies_clean():
    labels = []
    for label, sched in matrix.analysis_cells():
        diags = av.verify_schedule(sched, context=label)
        assert not diags, [d.render() for d in diags]
        labels.append(label)
    # the sweep must include what only the STATIC path can reach:
    # 512 workers, composed two-level (incl. the 512-chip 2x256
    # production mesh), and a three-axis fold
    assert any("/p512" in l for l in labels)
    assert any(l.startswith("composed/") and "/2x256" in l
               for l in labels)
    assert any(l.startswith("flat3/") for l in labels)
    # every codec'd analysis cell (incl. the 2x256 production mesh
    # under fp8) is part of the clean sweep above
    for strat, sizes, _, codec in matrix.ANALYSIS_CODEC_CELLS:
        mesh = "x".join(str(s) for s in sizes)
        assert f"codec/{strat}/{mesh}/{codec}" in labels
    # and the full characterization grid
    for d in matrix.DESIGNS:
        for p in matrix.WORKERS:
            assert any(l.startswith(f"{d}/") and l.endswith(f"/p{p}")
                       for l in labels)


def test_planner_schedules_verify_clean_all_strategies():
    import jax
    import jax.numpy as jnp
    tree = {"w": jax.ShapeDtypeStruct((4096, 64), jnp.float32),
            "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
    for strategy in ("rhd_rsa", "ring_rsa", "psum", "ps_gather"):
        for sizes in ((8,), (3,), (2, 8)):
            names = ("data",) if len(sizes) == 1 else ("pod", "data")
            s = sm.plan(tree, axis_names=names, axis_sizes=sizes,
                        strategy=strategy)
            assert rule_ids(s) == [], (strategy, sizes)
    for strategy in ("hierarchical", "ring_rsa×psum"):
        s = sm.plan(tree, axis_names=("pod", "data"), axis_sizes=(2, 8),
                    strategy=strategy)
        assert rule_ids(s) == [], strategy


def test_verify_summary_record_shape():
    rec = av.verify_summary(flat(), context="unit")
    assert rec["schema"] == "repro/analysis/v1"
    assert rec["n_errors"] == 0 and rec["n_warnings"] == 0
    assert rec["n_buckets"] == 2
    assert rec["wire_tolerance"] == pytest.approx(4 * 2 ** -24)
    json.dumps(rec)   # dryrun embeds it — must be JSON-clean


# ---------------------------------------------------------------------------
# hlo_lint
# ---------------------------------------------------------------------------

def _permute_sched(placement="post_backward"):
    return sm.synthetic([1 << 20], "ring_rsa", (4,), ("data",),
                        placement=placement)


def test_wire_check_wrapper_is_byte_identical():
    from repro.launch import roofline as rl
    s = _permute_sched()
    charged = {"collective-permute": s.total_wire_bytes,
               "all-reduce": 123}
    assert rl.wire_check(s, charged) == hlo_lint.wire_check(s, charged)
    assert rl.wire_check(s, charged)["consistent"]


def test_hl001_under_charged_bytes():
    s = _permute_sched()
    diags = hlo_lint.lint_hlo(
        s, collective_bytes={"collective-permute":
                             s.total_wire_bytes // 2})
    assert [d.rule_id for d in diags] == ["HL001"]
    assert diags[0].severity == ERROR


def test_hl002_overlap_must_interleave():
    s = _permute_sched(placement="in_backward")
    steps = hlo_lint.min_bucket_permute_steps(s)
    assert steps == 2 * (4 - 1)
    perms = [f"  %p{i} = f32[256] collective-permute(%x)"
             for i in range(steps)]
    dots = ["  %d1 = f32[8,8] dot(%a, %b)", "  %d2 = f32[8,8] dot(%c, %d)"]
    trailing = "\n".join(dots + perms)
    interleaved = "\n".join(perms + dots)
    assert any(d.rule_id == "HL002" for d in
               hlo_lint.lint_hlo(s, hlo_text=trailing,
                                 collective_bytes={}))
    assert not any(d.rule_id == "HL002" for d in
                   hlo_lint.lint_hlo(s, hlo_text=interleaved,
                                     collective_bytes={}))
    # post_backward schedules may legally trail
    assert not any(d.rule_id == "HL002" for d in
                   hlo_lint.lint_hlo(_permute_sched(),
                                     hlo_text=trailing,
                                     collective_bytes={}))


def test_hl003_mixed_dtype_reduction():
    s = _permute_sched()
    mixed = "  %r = f32[1024]{0} all-reduce(bf16[1024]{0} %x), to_apply=%add"
    pure = "  %r = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add"
    diags = hlo_lint.lint_hlo(s, hlo_text=mixed, collective_bytes={})
    hit = [d for d in diags if d.rule_id == "HL003"]
    assert hit and hit[0].location == "hlo:1"
    assert not any(d.rule_id == "HL003" for d in
                   hlo_lint.lint_hlo(s, hlo_text=pure,
                                     collective_bytes={}))
    # inline suppression comment disables the rule for this text
    suppressed = mixed + "\n// analysis-suppress: HL003\n"
    assert not any(d.rule_id == "HL003" for d in
                   hlo_lint.lint_hlo(s, hlo_text=suppressed,
                                     collective_bytes={}))


def test_hl004_unexpected_allreduce_is_baselinable_warning():
    s = _permute_sched()   # pure permute decomposition — no psum stage
    charged = {"collective-permute": s.total_wire_bytes,
               "all-reduce": 10 << 20}
    diags = hlo_lint.lint_hlo(s, collective_bytes=charged)
    assert [(d.rule_id, d.severity) for d in diags] == [("HL004", WARN)]
    # baseline accepts it; errors can never be baselined
    bl = [{"rule_id": "HL004", "context": "*"}]
    assert hlo_lint.unbaselined_warnings(diags, bl) == []
    err = Diagnostic("HL001", ERROR, "", "x")
    assert not hlo_lint.baselined(err, [{"rule_id": "HL001",
                                         "context": "*"}])
    # a psum schedule EXPECTS vendor all-reduce: no warning
    vendor = sm.synthetic([1 << 20], "psum", (4,), ("data",))
    assert hlo_lint.lint_hlo(vendor, collective_bytes={
        "all-reduce": 1 << 20}) == []


def test_committed_baseline_is_valid_and_empty():
    entries = hlo_lint.load_baseline(
        os.path.join(ROOT, hlo_lint.BASELINE_FILE))
    assert entries == []


# ---------------------------------------------------------------------------
# compat_lint
# ---------------------------------------------------------------------------

VIOLATIONS = textwrap.dedent("""\
    import jax
    from jax.experimental import shard_map          # CL001
    import jax.experimental.pjit as pjit_mod        # CL001
    from jax import lax

    def f(x):
        y = jax.lax.psum(x, "data")                 # CL002
        z = lax.ppermute(x, "data", [(0, 1)])       # CL002
        ok = lax.psum(x, "data")  # compat-lint: allow
        fine = jax.numpy.sum(x)
        pallas_ok = jax.experimental.pallas
        return y + z + ok + fine
""")


def test_compat_lint_flags_violations(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(VIOLATIONS)
    diags = compat_lint.lint_file(str(p), rel="bad.py")
    got = sorted((d.rule_id, int(d.location.split(":")[1]))
                 for d in diags)
    assert got == [("CL001", 2), ("CL001", 3), ("CL002", 7),
                   ("CL002", 8)], [d.render() for d in diags]


def test_compat_lint_source_tree_is_green():
    diags = compat_lint.lint_tree(ROOT)
    assert diags == [], [d.render() for d in diags]
    # scope sanity: compat.py itself is exempt, reducers.py is covered
    rels = [rel for _, rel in compat_lint.iter_source_files(ROOT)]
    assert os.path.join("src", "repro", "core", "reducers.py") in rels
    assert os.path.join("src", "repro", "core", "compat.py") not in rels


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=ROOT)


@pytest.mark.timeout(300)
def test_cli_schedule_json_gate(tmp_path):
    clean = flat().to_json()
    mutated = json.loads(json.dumps(clean))
    mutated["buckets"][0]["stages"][0]["wire_bytes"] += 64
    cp = tmp_path / "clean.json"
    mp = tmp_path / "mutated.json"
    cp.write_text(json.dumps(clean))
    mp.write_text(json.dumps(mutated))

    ok = _run_cli("--schedule-json", str(cp))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = _run_cli("--schedule-json", str(mp))
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "SV001" in bad.stdout


@pytest.mark.timeout(300)
def test_cli_source_mode_green_on_head(tmp_path):
    out = tmp_path / "diag.json"
    r = _run_cli("--source", "--check-baseline", "--json", str(out))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(out.read_text())
    assert rec["schema"] == "repro/analysis/v1"
    assert rec["n_errors"] == 0
