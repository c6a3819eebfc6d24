"""The benchmark's FLOPs function and peaks table."""
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import bench
import tiny


def _cell(name):
    return bench.load_cell(name, tiny.ROOT)


@pytest.mark.parametrize("workload,params,per_token,rounded", [
    # 32 x 9,830,400 + 49152 x 960 matmul weights; attention 6·32·960·2048
    ("smollm-360m.train-b6s2048", 361_758_720, 2_548_039_680.0, 2.55),
    # 10 x 60,817,408 + 49155 x 2048; attention 6·10·2048·4096
    ("granite-3-2b.train-b1s4096", 708_843_520, 4_756_377_600.0, 4.76),
])
def test_hand_counts(workload, params, per_token, rounded):
    cell = _cell(workload)
    assert cell.family.matmul_params(cell.cfg) == params
    assert cell.family.train_flops_per_token(cell.cfg, cell.seq) == \
        pytest.approx(per_token, rel=1e-12)
    assert round(per_token / 1e9, 2) == rounded


def test_dp4_counts_every_chip():
    four = _cell("smollm-360m.dp4-b4s1024")
    assert four.tokens_per_step == 4 * 4 * 1024
    # 2.36 GFLOP per token at 1024 positions
    per_token = 6 * 361_758_720 + 6 * 32 * 960 * 1024
    assert four.flops_per_step == pytest.approx(per_token * 16 * 1024)


def test_matmul_term_agrees_with_compiled_dots():
    """At a tiny spec without remat, the dot FLOPs XLA compiles into the
    train step are 6·N·T plus attention counted in full, 12·L·(h·hd)·S
    per token: twice the causal half the FLOPs function counts."""
    from repro.core.compat import make_mesh
    from repro.launch import hlo_analysis
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train import TrainStepConfig, make_train_step

    cell = tiny.cell()
    cell.cfg["program"] = dict(cell.cfg["program"], remat=False)
    fam, cfg = cell.family, cell.cfg
    model = build_model(fam.program_spec(cfg))
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    sds = jax.ShapeDtypeStruct((cell.global_batch, cell.seq), jnp.int32)
    batch = {"tokens": sds, "labels": sds}
    step, _ = make_train_step(model, adamw(1e-3), mesh,
                              TrainStepConfig(), batch, donate=False)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    o = jax.eval_shape(adamw(1e-3).init, p)
    text = step.lower(p, o, batch).compile().as_text()
    dots = hlo_analysis.analyze(text).flops
    m = fam.dims(cfg)
    attn_full = 12.0 * m["layers"] * m["h"] * m["hd"] * cell.seq
    want = (6.0 * fam.matmul_params(cfg) + attn_full) * cell.tokens_per_step
    # vocab 512 is its own padded table, so the head is counted alike
    assert m["rows"] == m["vocab"]
    assert dots == pytest.approx(want, rel=1e-9)
    half = fam.train_flops_per_token(cfg, cell.seq) * cell.tokens_per_step
    assert want - half == pytest.approx(attn_full / 2 * cell.tokens_per_step)


def test_peaks_table():
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert bench.peak_flops(v5e) == 197e12
    with open(os.path.join(bench.HERE, "peaks.json")) as f:
        assert "source" in json.load(f)["TPU v5 lite"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in peaks.json"):
        bench.peak_flops(types.SimpleNamespace(device_kind="TPU v9 huge"))
