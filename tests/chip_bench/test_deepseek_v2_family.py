"""The deepseek_v2 family (``families/deepseek_v2.py``): its hand-counted
FLOPs, its reference against the program at a tiny size on the CPU
(a sound run is correct, the control and the faults are not), and the
readers of the expert layer and the attention kernels on a synthetic
trace record."""
import json
import os
import time

import jax
import numpy as np
import pytest

import bench
import moe_scopes
import reference
import scopes
import tiny

WORKLOAD = "deepseek-v2-lite.train-b2s8192"
# Limits at the tiny size, from CPU readings over eight seeds: the
# program's largest loss, grad, grad_err and delta gaps 1.4e-4 / 7.6e-3
# / 0.053 / 4.0e-3; the float8 control's smallest over four 5.0e-4 /
# 0.033 / 0.40 / 0.011; the half-batch fault's 1.0e-3 / 0.053 / 0.75 /
# 0.025.  The cell's own limits, set on the chip at the timed size, are
# in benchmarks/chip/limits/.
TINY = {"loss_gap": {"limit": 3e-4}, "grad_gap": {"limit": 2e-2},
        "grad_err": {"limit": 0.15}, "delta_gap": {"limit": 8e-3}}


def _family():
    return bench._load_module(
        os.path.join(tiny.CHIP, "families", "deepseek_v2.py"),
        "family_deepseek_v2")


def _cell(limits=None):
    """The cell's configuration with every size cut: 3 layers (one
    dense), 4 of 16 routed experts held from expert 4, top-3, 64
    positions, two rows."""
    with open(os.path.join(tiny.CHIP, "configs",
                           "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               router_outputs=16, n_routed_experts=4, expert_first=4,
               num_experts_per_tok=3, num_hidden_layers=3, vocab_size=256)
    with open(os.path.join(tiny.CHIP, "mixes", "train-b2s8192.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=64, pool_batches=4)
    e2e = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    return bench.Cell("tiny-deepseek", 1, cfg, mix, _family(),
                      limits or {}, e2e, [])


def test_hand_counts():
    """Per layer: attention 2048·3072 + 2048·576 + 512·2048 + 512·2048 +
    2048·2048 = 13,762,560; the dense MLP 3·2048·10944 = 67,239,936; a
    MoE layer's router 131,072, shared experts 3·2048·2816 = 17,301,504
    and the held experts at 6·8/64 = 0.75 of one (8,650,752):
    6,488,064; the head 12800·2048 = 26,214,400."""
    cell = bench.load_cell(WORKLOAD, tiny.ROOT)
    n = 5 * 13_762_560 + 67_239_936 \
        + 4 * (131_072 + 17_301_504 + 6_488_064) + 26_214_400
    assert n == 257_949_696
    assert cell.family.matmul_params(cell.cfg) == n
    # causal MLA: 6 · 5 layers · 16 heads · (192 + 128) / 2 · 8192
    per_token = 6.0 * n + 6 * 5 * 16 * 320 / 2 * 8192
    assert per_token == 2_176_843_776.0
    assert cell.family.train_flops_per_token(cell.cfg, 8192) == per_token
    assert round(per_token / 1e9, 2) == 2.18
    assert cell.flops_per_step == pytest.approx(per_token * 2 * 8192)


def test_kernel_counts():
    """Held experts: 4 layers · 2·8192·6·8/64 = 12,288 pairs, 24·2048·1408
    FLOPs each.  Attention kernels: 5 layers · 2·16·8192·8193/2 visible
    pairs, 2·(2·320) in the forward and the recompute and 2·(2·128 +
    3·192) in the backward."""
    cell = bench.load_cell(WORKLOAD, tiny.ROOT)
    fam, cfg = cell.family, cell.cfg
    assert fam.expert_flops_per_step(cfg, 2, 8192) == \
        4 * 12_288 * 24 * 2048 * 1408
    pairs = 2 * 16 * 8192 * 8193 // 2
    assert fam.attn_kernel_flops_per_step(cfg, 2, 8192) == \
        5 * pairs * (2 * 640 + 1664)


def test_config_keeps_the_published_widths():
    cell = bench.load_cell(WORKLOAD, tiny.ROOT)
    cfg = cell.cfg
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 12800)
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64,
                                "vocab_size": 102400}
    spec = cell.family.program_spec(cfg)
    assert (spec.d_model, spec.num_heads, spec.kv_lora_rank,
            spec.qk_nope_dim, spec.qk_rope_dim, spec.v_head_dim,
            spec.moe_d_ff, spec.dense_d_ff, spec.num_experts, spec.top_k,
            spec.num_shared_experts) == (2048, 16, 512, 128, 64, 128, 1408,
                                         10944, 64, 6, 2)
    assert spec.held_experts == 8 and spec.expert_first == 0
    assert not spec.norm_topk_prob and spec.router_aux == "seq"


def test_reference_yarn_agrees_with_the_program():
    from repro.models import common
    cell = bench.load_cell(WORKLOAD, tiny.ROOT)
    spec = cell.family.program_spec(cell.cfg)
    np.testing.assert_allclose(
        cell.family.yarn_inv_freq(cell.cfg),
        common.rope_frequencies(64, spec.rope_theta, spec.rope_scaling),
        rtol=1e-6)


def _run(cell, **kw):
    return bench.run_cell(cell, jax.devices(), 2 ** 33 + 4242, 0.3, False,
                          time.perf_counter(), **kw)


def test_sound_run_is_correct():
    """The program's loss and first gradient through ``Trainer``, against
    the float32 reference, from the same seed and batches."""
    r = _run(_cell(TINY))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and set(r["checks"]) == set(TINY)


def test_control_is_not_correct():
    cell = _cell(TINY)
    r = _run(cell, prog=reference.control_program(cell, jax.devices()))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_fault_is_not_correct(fault):
    r = _run(_cell(TINY), fault=fault)
    assert not r["correct"], r["checks"]


# ---------------------------------------------------------------------------
# the readers, on a synthetic step
# ---------------------------------------------------------------------------

_HLO = """HloModule step

%fused_copy (p.1: bf16[8]) -> bf16[8] {
  %p.1 = bf16[8]{0} parameter(0)
  ROOT %copy.1 = bf16[8]{0} copy(%p.1)
}

ENTRY %main (p.0: bf16[8]) -> bf16[8] {
  %p.0 = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%p.0), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/moe/router/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/moe/dispatch/gather"}
  %ragged-dot.3 = bf16[8]{0} ragged-dot(%fusion.2), metadata={op_name="jit(step)/transpose(jvp())/moe/experts/ragged_dot_general"}
  %fusion.4 = bf16[8]{0} fusion(%ragged-dot.3), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/moe/combine/scatter-add"}
  %fusion.5 = bf16[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/moe/mlp/dot_general"}
  %flash_fwd.6 = bf16[8]{0} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/attention/sdpa/flash_fwd/pallas_call"}
  %fusion.7 = bf16[8]{0} fusion(%flash_fwd.6), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/attention/sdpa/transpose"}
  %all-reduce.8 = bf16[8]{0} all-reduce(%fusion.7), metadata={op_name="jit(step)/moe/psum"}
  ROOT %fusion.9 = bf16[8]{0} fusion(%all-reduce.8), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(step)/jvp()/head/dot_general"}
}
"""
_SECS = {"fusion.1": 1.0, "fusion.2": 2.0, "ragged-dot.3": 4.0,
         "fusion.4": 8.0, "fusion.5": 16.0, "flash_fwd.6": 32.0,
         "fusion.7": 64.0, "all-reduce.8": 128.0, "fusion.9": 256.0}


PEAK, HBM = 197e12, 819e9          # v5e, peaks.json


def _read(name, run):
    reader = bench._load_module(
        os.path.join(tiny.CHIP, "metrics", name + ".py"), "metric_" + name)
    return reader.read(run)


def _synthetic_run(monkeypatch, text=_HLO):
    """A traced run of two steps on two devices, the second twice as
    slow, of the full-size cell."""
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: text)
    trace = {"per_device": [{"per_op_s": dict(_SECS)},
                            {"per_op_s": {k: 2 * v for k, v in
                                          _SECS.items()}}]}
    return {"cell": bench.load_cell(WORKLOAD, tiny.ROOT), "peak": PEAK,
            "record": {"steps": 2, "trace": trace}}


def test_moe_and_kernel_readers(monkeypatch):
    run = _synthetic_run(monkeypatch)
    cell = run["cell"]
    # moe: router, dispatch, experts, combine and the shared mlp, 31 s on
    # the first device and 62 on the second, the all-reduce left out
    assert _read("moe_ms", run) == pytest.approx(1e3 * 46.5 / 2)
    # the least time of two steps, the larger of FLOPs over the peak and
    # bytes over the bandwidth (both kernels are bound by compute here),
    # over the time of the ops under ``experts`` or the kernels
    for name, prefix, secs in (("expert_roofline", "expert", 6.0),
                               ("attn_kernel_roofline", "attn_kernel",
                                48.0)):
        flops = getattr(cell.family, prefix + "_flops_per_step")(
            cell.cfg, 2, 8192) * 2
        bytes_ = getattr(cell.family, prefix + "_bytes_per_step")(
            cell.cfg, 2, 8192) * 2
        assert flops / PEAK > bytes_ / HBM
        assert _read(name, run) == pytest.approx(
            100 * flops / PEAK / secs)
    s = run["record"]["trace"][moe_scopes.KEY]
    assert s["parts_s"] == pytest.approx(
        {"router": 1.5, "dispatch": 3.0, "experts": 6.0, "combine": 12.0})

    def no_second_read(cell):
        raise AssertionError("the step's HLO was read twice")
    monkeypatch.setattr(scopes, "step_hlo", no_second_read)
    assert _read("moe_ms", run) == pytest.approx(1e3 * 46.5 / 2)


def test_readers_find_nothing_without_the_scopes(monkeypatch):
    """A program without the ``moe`` scope or kernels under ``sdpa`` (the
    parent of this family) and a run without a trace read nothing."""
    bare = _HLO.replace("/moe/", "/").replace("/sdpa/", "/") \
        .replace("custom-call", "fusion")
    run = _synthetic_run(monkeypatch, bare)
    for name in ("moe_ms", "expert_roofline", "attn_kernel_roofline"):
        assert _read(name, run) is None
        assert _read(name, {"cell": run["cell"],
                            "record": {"steps": 3}}) is None
