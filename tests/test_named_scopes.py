"""The named scopes of the compiled train step (DESIGN.md §3.11): every
op the program writes into the step falls in a phase or a scope that the
on-chip benchmark's device-trace reduction reads
(``benchmarks/chip/scopes.py``), so the device time of a step splits
into forward, backward, recompute, optimizer and aggregation."""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import scopes  # noqa: E402
import trace_reduce as tr  # noqa: E402

# A two-layer remat step at tiny widths, data parallel over two host
# devices, AdamW; its compiled HLO text goes to the file named last.
_STEP = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {src!r})
import dataclasses
import jax
from repro.configs import get_spec
from repro.core import AggregatorConfig
from repro.core.compat import make_mesh
from repro.data.synthetic import SyntheticText
from repro.models import build_model
from repro.optim import adamw
from repro.train import TrainStepConfig, make_train_step
import repro.models.attention as A

# the TPU route of the attention core, its kernels interpreted here
A.on_tpu = lambda: {kernel}
spec = dataclasses.replace(get_spec("smollm-360m").reduced(), num_layers=2,
                           remat=True)
model = build_model(spec)
data = SyntheticText(spec.vocab_size, batch=4, seq_len={seq})
opt = adamw(1e-3)
cfg = TrainStepConfig(aggregator=AggregatorConfig(strategy="rhd_rsa"),
                      dp_axes=("data",))
step_fn, _ = make_train_step(model, opt, make_mesh((2,), ("data",)), cfg,
                             data.batch_at(0), donate=False)
# a cached executable of another version would carry that version's
# names; one compiled from another call site is the same program
assert jax.config.jax_compilation_cache_include_metadata_in_key
assert jax.config.jax_traceback_in_locations_limit == 0
params = model.init(jax.random.PRNGKey(0))
text = step_fn.lower(params, opt.init(params),
                     data.batch_at(0)).compile().as_text()
with open({out!r}, "w") as f:
    f.write(text)
"""

# Ops outside every phase and scope, by their path under the step's
# root: what the compiler made itself (no op name: layout copies,
# converts, bitcasts), copies of the loop carries at the root, the
# loss/metric means over the data axis, and the zero cotangent buffers
# of the scanned layers' backward.
ROOT_PATH = "jit(local_step)/shard_map"
ALLOWED = re.compile(r"^(|psum|div|broadcast\.\d+)$")


def _executed(text: str):
    """(name, opcode, op_name) of each instruction in the computations
    that run as steps of the program (the entry and the loops and calls
    it reaches; not the computations a fusion or a reduce applies),
    leaving out parameters, tuples and constants."""
    parsed = tr.parse_hlo(text)
    instrs, comps = parsed["instrs"], parsed["comps"]
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for n in comps.get(comp, ()):
            if instrs[n]["opcode"] in tr.CONTAINERS:
                todo += instrs[n]["calls"]
    skip = ("parameter", "get-tuple-element", "tuple", "constant")
    return [(n, instrs[n]["opcode"], instrs[n]["op_name"])
            for comp in seen for n in comps[comp]
            if instrs[n]["opcode"] not in skip + tr.CONTAINERS]


def _step_text(seq, kernel, tmp_path) -> str:
    out = str(tmp_path / "step.hlo.txt")
    code = _STEP.format(src=os.path.join(ROOT, "src"), seq=seq, out=out,
                        kernel=kernel)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=280)
    assert p.returncode == 0, p.stderr[-3000:]
    with open(out) as f:
        return f.read()


def _check_phases_and_scopes(text):
    """Every op in a phase or scope; the attention core in the forward,
    the recompute and the backward.  Returns the executed ops."""
    ops = _executed(text)
    loose = []
    for name, opcode, path in ops:
        if scopes.phase(path) != "unscoped" \
                or scopes.names(path) & set(scopes.SCOPES):
            continue
        rel = path[len(ROOT_PATH) + 1:] if path.startswith(ROOT_PATH) \
            else path
        if not ALLOWED.match(rel):
            loose.append((name, opcode, path))
    assert not loose, loose[:10]
    hlo = tr.read_hlo(text)
    assert {"sdpa", "aggregate", "optimizer", "clip"} <= \
        set(scopes.present(hlo))
    phases = {scopes.phase(path) for _, _, path in ops}
    assert set(scopes.PHASES) - {"unscoped"} <= phases
    # the attention core runs in the forward, the recompute and the
    # backward (for the flash paths: their custom backward)
    core = {scopes.phase(path) for _, _, path in ops
            if scopes.ATTN_CORE in scopes.names(path)}
    assert {"forward", "recompute", "backward"} <= core
    return ops


# Plain attention (16 positions), and the chunked flash path with its
# custom backward (128 positions, past the reduced spec's
# attn_full_seq_max of 64).
@pytest.mark.timeout(300)
@pytest.mark.parametrize("seq", [16, 128])
def test_every_op_of_the_step_is_in_a_phase_or_scope(seq, tmp_path):
    _check_phases_and_scopes(_step_text(seq, False, tmp_path))


@pytest.mark.timeout(300)
def test_kernel_path_sits_under_sdpa_in_every_phase(tmp_path):
    """The TPU route (128 positions, one block of the Pallas kernel; its
    kernels interpreted here): the forward kernel runs under ``sdpa`` in
    the forward and in the recompute, the dq and dk/dv kernels in the
    backward, so ``attn_core_ms`` and the phases keep measuring the
    same ops."""
    ops = _check_phases_and_scopes(_step_text(128, True, tmp_path))
    kernels = {(scopes.phase(path), k) for _, _, path in ops
               if scopes.ATTN_CORE in scopes.names(path)
               for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
               if k in scopes.names(path)}
    assert kernels == {("forward", "flash_fwd"), ("recompute", "flash_fwd"),
                       ("backward", "flash_bwd_dq"),
                       ("backward", "flash_bwd_dkv")}
