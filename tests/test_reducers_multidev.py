"""Multi-device reducer/aggregator/train correctness — each check file
runs as one subprocess with forced host devices (the main pytest
process stays at 1 device). The runner passes the device count through
the REPRO_TEST_DEVICES env hook (see tests/devflags.py and
tests/README.md) instead of each script hand-rolling XLA_FLAGS."""
import os
import subprocess
import sys

import pytest


def _run_checks(script_name: str, devices: int, sentinel: str):
    script = os.path.join(os.path.dirname(__file__), script_name)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["REPRO_TEST_DEVICES"] = str(devices)
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=880, env=env)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    assert sentinel in proc.stdout


@pytest.mark.timeout(900)
def test_multidev_checks():
    _run_checks("multidev_checks.py", 8, "ALL MULTIDEV CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_nonpow2_checks():
    """rhd_rsa on p ∈ {3, 4, 6, 8, 12}: bit-exact vs psum, compiled to
    the RHD ppermute schedule (no ring/psum fallback), and hierarchical
    over a non-pow2 pod axis — deviation D2 removal."""
    _run_checks("multidev_nonpow2_checks.py", 12,
                "ALL NONPOW2 CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_mixed_strategy_checks():
    """strategy='auto' per-bucket selection on p ∈ {3, 4, 6, 8}:
    empirically-forced rhd+psum mix and the p=6 analytic rhd+ring mix
    are bit-exact with psum, the compiled HLO contains both schedules,
    and a real train step mixes ≥ 2 algorithms."""
    _run_checks("multidev_mixed_strategy_checks.py", 8,
                "ALL MIXED STRATEGY CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_experiments_checks():
    """Measured backend of the characterization matrix on p ∈ {3, 4, 8}:
    real reducer wall-clock composed through the model's timeline, with
    the No-gRPC-beats-gRPC_PS ordering; the hierarchical two-level HLO
    wire decomposition; and the roofline.wire_check consistency layer
    against a real compiled step."""
    _run_checks("multidev_experiments_checks.py", 8,
                "ALL EXPERIMENTS CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_hierarchical_overlap_checks():
    """Composed per-level schedules × overlap (ReduceSchedule IR,
    DESIGN.md §3.8) on (d, pods) ∈ {(2,2), (2,3), (4,2)}: fixed
    ring_rsa×rhd_rsa under overlap=True bit-exact vs post-backward and
    psum; per-bucket flat+composed mix from an axes-aware tuning table
    with both levels in the HLO, permute bytes == the IR's per-stage
    wire bytes, and roofline.wire_check PASS."""
    _run_checks("multidev_hierarchical_overlap_checks.py", 8,
                "ALL HIERARCHICAL OVERLAP CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_codec_checks():
    """Wire-codec numerics wall (DESIGN.md §3.10) on p ∈ {3, 4, 6, 8}:
    int8/fp8 allreduce within the DERIVED tolerance of psum
    (verify.codec_tolerance of the executed schedule), bf16 codec
    bit-identical to the wire_dtype path on bf16-exact data, the EF
    residual equal to the quantization error, a real auto train step
    mixing codec'd and uncodec'd buckets, and HLO permute bytes ==
    Σ encoded IR wire bytes with roofline.wire_check PASS."""
    _run_checks("multidev_codec_checks.py", 8,
                "ALL CODEC CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_fused_hop_checks():
    """Fused-hop execution wall (DESIGN.md §3.13) on p ∈ {3, 4, 6, 8}:
    the fused decode→accumulate→encode route bit-exact vs the unfused
    stage walk for none/bf16 wires and within 2^-20·absmax (FMA
    contraction) for int8/fp8; StageExecutor cache hit on the second
    identical request with zero retraces and donated inputs consumed;
    and the dynamic-slice ring reduce-scatter bit-exact vs psum on
    integer-valued data."""
    _run_checks("multidev_fused_hop_checks.py", 8,
                "ALL FUSED HOP CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_three_axis_checks():
    """Three-level composed schedules on the (2, 2, 2)
    (pod × data × model) mesh — the full-manual lowering's model
    bracket (DESIGN.md §3.12): ``ring@data×rhd@pod×ag@model`` bit-exact
    vs dp psum, HLO permute bytes == Σ per-stage IR wire bytes with
    wire_check PASS, and a real train step on the three-axis mesh
    matching the GSPMD partial-auto lowering."""
    _run_checks("multidev_three_axis_checks.py", 8,
                "ALL THREE-AXIS CHECKS PASSED")


@pytest.mark.timeout(900)
def test_multidev_overlap_checks():
    """overlap=True (in-backward per-bucket reductions) on
    p ∈ {3, 4, 6, 8}: bit-exact with the post-backward path and with
    psum, composes with mixed auto schedules, trains identically, and
    every rank reports the single-process global gradient norm
    (clip-after-aggregation fix)."""
    _run_checks("multidev_overlap_checks.py", 8,
                "ALL OVERLAP CHECKS PASSED")
