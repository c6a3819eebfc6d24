"""The ``attn_kernel_pct`` reader (``metrics/attn_kernel_pct.py``): the
share of the attention core's device time that runs in Pallas custom
calls, on a synthetic step and on the traces recorded on v5e chips
before the kernel was on the path."""
import gzip
import os
import shutil

import pytest

import bench
import record_trace
import scopes
import tiny
import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(tr.__file__), "testdata")

# A step with the attention core in Pallas kernels: a layout fusion and
# two kernels under ``sdpa``, and a custom call and a fusion outside it.
_KERNEL_HLO = """HloModule step

%fused_copy (p.1: bf16[8]) -> bf16[8] {
  %p.1 = bf16[8]{0} parameter(0)
  ROOT %copy.1 = bf16[8]{0} copy(%p.1)
}

ENTRY %main (p.0: bf16[8]) -> bf16[8] {
  %p.0 = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%p.0), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/attention/sdpa/transpose"}
  %flash_fwd.2 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/attention/sdpa/flash_fwd/pallas_call"}
  %flash_bwd_dq.3 = bf16[8]{0} custom-call(%flash_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/attention/sdpa/flash_bwd_dq/pallas_call"}
  %custom-call.4 = bf16[8]{0} custom-call(%flash_bwd_dq.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/mlp/dot_general"}
  ROOT %fusion.5 = bf16[8]{0} fusion(%custom-call.4), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/mlp/mul"}
}
"""
_SECS = {"fusion.1": 1.0, "flash_fwd.2": 3.0, "flash_bwd_dq.3": 4.0,
         "custom-call.4": 100.0, "fusion.5": 50.0}


def _read(run):
    reader = bench._load_module(
        os.path.join(tiny.CHIP, "metrics", "attn_kernel_pct.py"),
        "metric_attn_kernel_pct")
    return reader.read(run)


def _synthetic_run(text, monkeypatch):
    """A traced run of a program whose compiled step is ``text``, on two
    devices, the second three times as slow."""
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: text)
    trace = {"per_device": [{"per_op_s": dict(_SECS)},
                            {"per_op_s": {k: 3 * v for k, v in
                                          _SECS.items()}}]}
    return {"cell": None, "record": {"steps": 2, "trace": trace}}


@pytest.mark.parametrize("scoped,want", [(True, 87.5), (False, None)])
def test_share_of_a_synthetic_step(scoped, want, monkeypatch):
    """Custom calls under ``sdpa`` over every op under it, whatever the
    phase; nothing to read where no op carries the scope.  The HLO is
    read once per run, and the scope record kept beside the share."""
    text = _KERNEL_HLO if scoped else _KERNEL_HLO.replace("/sdpa/", "/")
    run = _synthetic_run(text, monkeypatch)
    got = _read(run)
    assert got == (pytest.approx(want, rel=1e-12) if want else None)
    assert run["record"]["trace"]["scopes"]["present"] == \
        (["attention", "mlp", "sdpa"] if scoped else ["attention", "mlp"])

    def no_second_read(cell):
        raise AssertionError("the step's HLO was read twice")
    monkeypatch.setattr(scopes, "step_hlo", no_second_read)
    assert _read(run) == got


def test_nothing_to_read_without_a_trace():
    assert _read({"record": {"steps": 3}}) is None


def _recorded(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("attn") / (name + ".xplane.pb")
    with gzip.open(os.path.join(TESTDATA, name + ".xplane.pb.gz"),
                   "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with gzip.open(os.path.join(TESTDATA, name + ".hlo.txt.gz"),
                   "rt") as f:
        text = f.read()
    return str(path), text


@pytest.mark.parametrize("name,want", [("dp4_scoped", 0.0),
                                       ("dp4_tiny", None)])
def test_recorded_traces(name, want, tmp_path_factory, monkeypatch):
    """The scoped program's attention core, recorded before the kernel
    was on the path, reads 0; the program without scopes has nothing to
    read."""
    path, text = _recorded(name, tmp_path_factory)
    t = tr.reduce_trace(path, tr.read_hlo(text), bench.HOST_LABELS)
    run = {"cell": record_trace.tiny_cell(4),
           "record": {"steps": 2, "trace": t}}
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: text)
    assert _read(run) == want
