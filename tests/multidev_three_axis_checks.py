"""Three-level composed schedules on a (pod × data × model) mesh — the
full-manual lowering's model bracket (DESIGN.md §3.12), run as a
SUBPROCESS by test_reducers_multidev.py with 8 host devices.

The production configuration the partial-auto ceiling used to SKIP: a
manual ``model`` axis composing with the two dp levels into a
three-level per-bucket schedule, e.g. ``ring@data×rhd@pod×ag@model``
(shard over model → dp reduction on the 1/m chunk → all-gather over
model).  Pins, on the (2, 2, 2) ("pod", "data", "model") host mesh:

  * a fixed ``ring_rsa×rhd_rsa`` aggregator with ``model_axis="model"``
    is BIT-EXACTLY equal to a plain dp ``psum`` on integer-valued
    float32 gradients — the bracket changes where each dp-sum term is
    computed (1/m per model rank), never the per-element add order;
  * the compiled HLO contains ONLY explicit collectives, and its
    collective-permute bytes equal the IR's summed per-stage wire
    bytes — the third level's ``(m-1)/m`` all-gather chunk included;
  * ``roofline.wire_check`` PASSES against the same ReduceSchedule
    object the aggregator executed, with the zero-wire ``shard``
    opener excluded from the predicted side;
  * a REAL train step (reduced smollm) on the three-axis mesh takes the
    full-manual path, trains (finite loss that falls on a repeated
    batch), renders the three-level decomposition, and matches the GSPMD
    partial-auto lowering (``legacy_partial_auto=True``) numerically.

Exit code 0 = all checks passed."""
from devflags import force_host_devices

force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import AggregatorConfig, GradientAggregator, PlanCache
from repro.core.compat import shard_map
from repro.core.reducers import allreduce_steps

PODS, D, M = 2, 2, 2
DP_AXES = ("pod", "data")


def make_mesh3():
    devs = jax.devices()
    return Mesh(np.array(devs[:PODS * D * M]).reshape(PODS, D, M),
                ("pod", "data", "model"))


def int_loss(params, x):
    """Loss whose per-rank gradients are integer-valued float32: every
    summation order is exact, so bit-equality is the bar."""
    s = jnp.sum(x)
    total = 0.0
    for k in sorted(params):
        v = params[k]
        coeff = s + jnp.arange(v.size, dtype=jnp.float32).reshape(v.shape)
        total = total + jnp.sum(v * coeff)
    return total


def int_params():
    """Element counts are multiples of lcm(D, M, rhd core) so neither
    the ring chunking, the model-bracket shard, nor the RHD fold pads."""
    return {
        "a": jnp.ones((64, 3), jnp.float32),
        "b": jnp.ones((64,), jnp.float32),
        "w": jnp.ones((12288,), jnp.float32),
    }


def grads_fn(cfg, mesh, model_axis):
    agg = GradientAggregator(cfg, DP_AXES, cache=PlanCache(),
                             model_axis=model_axis)

    def local(params, x):
        g = jax.grad(int_loss)(params, x)
        return agg(g)

    # every axis manual
    fn = jax.jit(shard_map(local, mesh, in_specs=(P(), P(DP_AXES)),
                           out_specs=P(), axis_names=None,
                           check_vma=False))
    return fn, agg


def check_bracket_bitexact_vs_psum():
    mesh = make_mesh3()
    params = int_params()
    x = jnp.arange(PODS * D * 4, dtype=jnp.float32)
    comp = AggregatorConfig(strategy="ring_rsa×rhd_rsa",
                            fusion_threshold_mb=0.02)
    ref = AggregatorConfig(strategy="psum", fusion_threshold_mb=0.02)
    fn_br, agg = grads_fn(comp, mesh, model_axis="model")
    fn_ref, _ = grads_fn(ref, mesh, model_axis=None)
    g_br, g_ref = fn_br(params, x), fn_ref(params, x)
    sched = agg.last_schedule
    assert sched.model_axis == "model", sched.to_json()
    assert sched.model_axis_size == M
    assert all(b.render() == "ring@data×rhd@pod×ag@model"
               for b in sched.buckets), sched.render()
    for k in params:
        assert (np.asarray(g_br[k]) == np.asarray(g_ref[k])).all(), \
            f"three-level bracket != dp psum bit-exactly at {k!r}"
    print(f"bracket bit-exact vs psum ok ({sched.render()})")


def check_hlo_bytes_and_wire_check():
    from repro.launch import hlo_analysis as H
    from repro.launch import roofline as rl

    mesh = make_mesh3()
    params = int_params()
    x = jnp.arange(PODS * D * 4, dtype=jnp.float32)
    comp = AggregatorConfig(strategy="ring_rsa×rhd_rsa",
                            fusion_threshold_mb=0.02)
    fn, agg = grads_fn(comp, mesh, model_axis="model")
    fn(params, x)
    sched = agg.last_schedule

    txt = fn.lower(params, x).compile().as_text()
    assert "all-reduce" not in txt, \
        "explicit schedules only — no vendor collective"
    # per bucket: ring RS+AG over data, RHD over pods, ring AG over model
    want_perm = len(sched.buckets) * (
        2 * (D - 1) + allreduce_steps("rhd_rsa", PODS) + (M - 1))
    n_perm = txt.count("collective-permute(")
    assert n_perm == want_perm, (n_perm, want_perm, sched.render())

    charged = H.analyze(txt).collective_bytes
    got = charged.get("collective-permute", 0)
    want = sum(st.wire_bytes for b in sched.buckets for st in b.stages)
    assert got == want, (got, want, sched.to_json())
    # the shard opener is local: zero wire bytes, no HLO kind
    openers = [b.stages[0] for b in sched.buckets]
    assert all(st.op == "shard" and st.wire_bytes == 0
               and st.hlo_kind is None for st in openers)
    # third level charges the (m-1)/m chunk per bucket
    for b in sched.buckets:
        ag = b.stages[-1]
        assert ag.op == "all_gather" and ag.axis == "model"
        assert ag.wire_bytes == (M - 1) * ag.n_bytes, b.to_json()

    rep = rl.wire_check(sched, charged)
    assert rep["consistent"], rep
    kind = rep["kinds"]["collective-permute"]
    assert kind["predicted"] == kind["charged"], rep
    print(f"hlo bytes + wire_check ok ({n_perm} permutes, "
          f"{want} wire bytes)")


def check_real_train_step_three_axis():
    from repro.configs import get_spec
    from repro.core.compat import make_mesh
    from repro.data.synthetic import SyntheticText
    from repro.models import build_model
    from repro.optim import sgd
    from repro.train import TrainStepConfig, make_train_step

    mesh = make_mesh((PODS, D, M), ("pod", "data", "model"))
    spec = get_spec("smollm-360m").reduced()
    model = build_model(spec)
    data = SyntheticText(spec.vocab_size, batch=8, seq_len=16)

    def run(**kw):
        opt = sgd(1e-2)
        cfg = TrainStepConfig(
            aggregator=AggregatorConfig(strategy="rhd_rsa"),
            dp_axes=DP_AXES)
        step_fn, sh = make_train_step(model, opt, mesh, cfg,
                                      data.batch_at(0), donate=False,
                                      **kw)
        params = model.init(jax.random.PRNGKey(1))
        opt_state = opt.init(params)
        # One repeated batch: the loss on it must fall step over step
        # (fresh random batches at lr 1e-2 are noise-dominated).
        batch = data.batch_at(0)
        losses = []
        for _ in range(4):
            params, opt_state, m = step_fn(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return params, losses, sh

    p_man, losses, sh = run()
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    agg = sh["aggregator"]
    assert agg.model_axis == "model"
    render = agg.last_schedule.render()
    assert "ag@model" in render, render

    # the GSPMD partial-auto lowering trains the same model
    p_leg, _, _ = run(legacy_partial_auto=True)
    for (ka, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(p_man),
                               jax.tree_util.tree_leaves_with_path(p_leg)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-3, atol=5e-5,
            err_msg=f"manual diverged from legacy partial-auto at {ka}")
    print(f"real three-axis train step ok ({render}; "
          f"{losses[0]:.3f} -> {losses[-1]:.3f})")


if __name__ == "__main__":
    check_bracket_bitexact_vs_psum()
    check_hlo_bytes_and_wire_check()
    check_real_train_step_three_axis()
    print("ALL THREE-AXIS CHECKS PASSED")
