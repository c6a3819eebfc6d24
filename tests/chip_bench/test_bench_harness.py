"""The harness end to end on the CPU, at a tiny size, with the look for
a chip skipped: a sound run is correct; the control in the program's
place, and each fault planted underneath the timed path, is not."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import bench
import reference
import tiny

# Limits at the tiny size, from CPU readings over eight seeds (one
# device / four host devices): the program's largest loss, grad and
# delta gaps 6.7e-5 / 3.2e-3 / 1.7e-3 (4 devices: 3.9e-5 / 2.1e-3 /
# 2.1e-3); the float8 control's smallest 2.8e-4 / 6.5e-3 / 4.4e-3
# (1.1e-4 / 4.4e-3 / 3.2e-3); every fault reads above 9e-4 on the loss
# or 1e-2 on a norm.  The cells' own limits, set on the chip at the
# timed sizes, are in benchmarks/chip/limits/.
# grad_err over three seeds: the program 2.0e-2 to 2.1e-2, the control
# 0.23 to 0.24, the half-batch fault 0.79 or more.
TINY = {"loss_gap": {"limit": 1e-4}, "grad_gap": {"limit": 4e-3},
        "grad_err": {"limit": 6e-2}, "delta_gap": {"limit": 3e-3}}
# One row of 32 tokens per step (granite's mix, cut), over eight seeds
# on one device: the program's largest gaps 1.0e-4 / 3.4e-3 / 1.9e-3,
# the control's smallest 5.3e-4 / 1.7e-2 / 3.6e-3, the half-batch
# fault's 2.4e-3 / 5.4e-2 / 9.4e-3.
# grad_err: the program 1.7e-2 to 1.9e-2, the control 0.21 or more.
TINY_ROW = {"loss_gap": {"limit": 2.5e-4}, "grad_gap": {"limit": 8e-3},
            "grad_err": {"limit": 6e-2}, "delta_gap": {"limit": 3e-3}}
MIXES = {"train-b6s2048": TINY, "train-b1s4096": TINY_ROW}
RUN = os.path.join(tiny.CHIP, "run.py")


def _run(cell, trace=False, **kw):
    devices = jax.devices()
    return bench.run_cell(cell, devices, 2 ** 33 + 12345, 0.3, trace,
                          time.perf_counter(), **kw)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "smollm-360m.train-b6s2048",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tiny.ROOT,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_require_devices_counts_chips(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert len(bench.require_devices(1)) == 1
    with pytest.raises(bench.NoChip, match="needs 4 chips"):
        bench.require_devices(4)


def test_memory_peak_counts_the_reserved_region():
    class Dev:
        def __init__(self, used, reserved):
            self.stats = {"peak_bytes_in_use": used,
                          "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self.stats
    assert bench.memory_peak([Dev(4, 7), Dev(9, 1)]) == 11


def test_inputs_come_from_the_seed():
    cell = tiny.cell()
    prog = bench.build_program(cell, jax.devices())
    mk = bench.makers(prog, cell)
    big = 2 ** 31 + 2 ** 33 + 5           # more than 32 signed bits
    a, b, c = (jax.device_get(mk.pool(bench.seed_words(s)))
               for s in (big, big, big + 1))
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert not (a[0]["tokens"] == c[0]["tokens"]).all()
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)   # all differ
    assert (a[0]["labels"][:, :-1] == a[0]["tokens"][:, 1:]).all()
    pa = jax.device_get(mk.state(bench.seed_words(big))[0])
    pb = jax.device_get(mk.state(bench.seed_words(big))[0])
    assert (pa["embed"] == pb["embed"]).all()


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sound_run_is_correct(mix):
    r = _run(tiny.cell(limits=MIXES[mix], mix_name=mix))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(r)[-5:] == ["checks", "_window_compiles", "_reference_s",
                            "_setup_phases", "_memory_stats"]
    assert r["_window_compiles"] == 0


def test_compare_reads_the_gradient_itself():
    """A gap of norms misses a gradient whose elements moved while its
    norm did not; grad_err, the norm of the difference, sees it."""
    g = {"a": np.array([3.0, 4.0], np.float32),
         "b": np.array([1.0, 0.0], np.float32)}
    norms = np.array([5.0, 1.0])
    ref = {"losses": np.array([2.0]), "grad": norms, "grad_raw": norms,
           "grad_tree": g, "delta": norms}
    swapped = dict(g, a=np.array([4.0, 3.0], np.float32))
    got = bench.Readings(np.array([2.0]), norms, swapped, norms)
    c = {k: v["value"] for k, v in bench.compare(got, ref, {}).items()}
    assert c["grad_gap"] == 0.0 and c["delta_gap"] == 0.0
    # |(1, -1)| over the larger of leaf a's norm and the median, 5
    assert c["grad_err"] == pytest.approx(np.sqrt(2.0) / 5.0)


def test_control_is_not_correct():
    """The reference with its matmul operands in float8, in the
    program's place, fails the limits."""
    cell = tiny.cell(limits=TINY)
    r = _run(cell, prog=reference.control_program(cell, jax.devices()))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_fault_is_not_correct(fault, mix):
    r = _run(tiny.cell(limits=MIXES[mix], mix_name=mix), fault=fault)
    assert not r["correct"], r["checks"]


def test_traced_run_reads_per_layer_metrics(monkeypatch):
    """A ``--trace 1`` run through the harness, the device trace's
    reduction stood in for (the CPU has no TPU plane): each per-layer
    reader gets the model FLOPs, the executed matmul FLOPs, the FLOP
    peak and the traced steps."""
    window, busy, matmul, executed = 2.0, 1.5, 1.0, 3e11
    reduced = {"window_s": window, "busy_s": busy, "matmul_s": matmul,
               "matmul_flops": executed, "collective_s": 0.0,
               "exposed_s": 0.0,
               "breakdown": {"device_ops": [["fusion.1 [matmul]", 1.0]],
                             "idle_gaps": [["sync", 0.5]]}}
    monkeypatch.setattr(bench, "reduce_trace", lambda *a: reduced)
    peak = 1e12
    monkeypatch.setattr(bench, "peak_flops", lambda device: peak)
    cell = tiny.cell(limits=TINY)
    cell.per_layer = [{"name": n, "unit": "%"} for n in
                      ("mfu_pct", "matmul_roofline", "device_idle_pct",
                       "agg_ms")]
    r = _run(cell, trace=True)
    assert r["correct"], r["checks"]
    steps = bench.TRACE_STEPS
    assert r["attempted"] == steps
    work = cell.flops_per_step * steps
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m == pytest.approx({
        "mfu_pct": 100 * work / (peak * window),
        "matmul_roofline": 100 * executed / (peak * matmul),
        "device_idle_pct": 100 * (1 - busy / window)})   # no agg_ms
    assert r["device"]["busy_s"] == busy
    assert r["device"]["window_s"] == window
    assert r["breakdown"] == reduced["breakdown"]


_FOUR = r"""
import json, sys, time
sys.path[:0] = {paths!r}
import jax, bench, tiny
cell = tiny.cell(4, {limits!r})
out = {{}}
for fault in ("", "no_exchange"):
    r = bench.run_cell(cell, jax.devices(), 2 ** 33 + 99, 0.3, False,
                       time.perf_counter(), fault=fault)
    out[fault or "sound"] = [r["correct"], r["checks"]]
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct():
    """On four host devices: the sound data-parallel run is correct, and
    the run whose aggregator leaves out the exchange is not."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = _FOUR.format(paths=[here, tiny.CHIP,
                               os.path.join(tiny.ROOT, "src")],
                        limits=TINY)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][0], out["sound"][1]
    assert not out["no_exchange"][0], out["no_exchange"][1]
