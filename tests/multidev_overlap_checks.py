"""Overlapped-aggregation checks (AggregatorConfig(overlap=True)), run
as a SUBPROCESS by test_reducers_multidev.py with 8 host devices.

Pins the overlap subsystem end to end:

  * for p ∈ {3, 4, 6, 8}: gradients computed with per-bucket reductions
    issued INSIDE the backward (``overlap_params`` custom_vjp
    boundaries) are BIT-EXACTLY equal to the post-backward path and to
    an all-``psum`` aggregator on integer-valued float32 — overlapping
    changes when collectives run, never what they compute;
  * at p=8 the overlap path composes with ``strategy="auto"`` mixed
    per-bucket schedules (forced rhd+psum table) and stays bit-exact;
  * a real train step with ``overlap=True`` on the partial-auto
    (data × model) mesh trains identically to ``overlap=False``;
  * the clip-by-global-norm fix: every rank reports the SAME gradient
    norm, and it equals the single-process global-batch norm
    (synchronous-SGD semantics) — the seed clipped each rank by its own
    shard's norm.

Exit code 0 = all checks passed."""
from devflags import force_host_devices

force_host_devices(8)

import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import AggregatorConfig, GradientAggregator, PlanCache
from repro.core import selector as sel
from repro.core.compat import make_mesh, shard_map
from repro.optim import clip_by_global_norm, global_norm


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


def int_params(p):
    """Several small fused leaves + one larger bucket; leading dims are
    multiples of lcm(core, p) so no reducer padding blurs equality."""
    return {
        "a": jnp.ones((p * 8, 3), jnp.float32),
        "b": jnp.ones((p * 4,), jnp.float32),
        "w": jnp.ones((p * 12288,), jnp.float32),
    }


def grads_fn(cfg, mesh, overlap):
    agg = GradientAggregator(cfg, ("data",), cache=PlanCache())

    def local(params, x):
        if overlap:
            return jax.grad(
                lambda q: int_loss(agg.overlap_params(q), x))(params)
        g = jax.grad(int_loss)(params, x)
        return agg(g)

    fn = jax.jit(shard_map(local, mesh, in_specs=(P(), P("data")),
                           out_specs=P(), axis_names={"data"},
                           check_vma=False))
    return fn, agg


def check_overlap_bitexact():
    devs = jax.devices()
    for p in (3, 4, 6, 8):
        mesh = Mesh(np.array(devs[:p]), ("data",))
        params = int_params(p)
        # per-rank distinct integer data
        x = jnp.arange(p * 4, dtype=jnp.float32)
        rhd = AggregatorConfig(strategy="rhd_rsa",
                               fusion_threshold_mb=0.02)
        ref = AggregatorConfig(strategy="psum", fusion_threshold_mb=0.02)
        fn_ov, agg_ov = grads_fn(rhd, mesh, overlap=True)
        fn_post, _ = grads_fn(rhd, mesh, overlap=False)
        fn_ref, _ = grads_fn(ref, mesh, overlap=False)
        g_ov, g_post, g_ref = fn_ov(params, x), fn_post(params, x), \
            fn_ref(params, x)
        assert agg_ov.last_schedule.n_buckets >= 2, \
            agg_ov.last_schedule.to_json()
        for k in params:
            a = np.asarray(g_ov[k])
            assert (a == np.asarray(g_post[k])).all(), \
                f"p={p}: overlap != post-backward bit-exactly at {k!r}"
            assert (a == np.asarray(g_ref[k])).all(), \
                f"p={p}: overlap != psum bit-exactly at {k!r}"
    print("overlap bit-exact (p=3,4,6,8) ok")


def check_overlap_mixed_strategies():
    """overlap=True composes with strategy='auto': a forced table mixes
    rhd (small fused bucket) + psum (big bucket) inside the backward,
    still bit-exact with all-psum."""
    p = 8
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
    params = int_params(p)
    x = jnp.arange(p * 4, dtype=jnp.float32)
    table = {"schema": sel.TABLE_SCHEMA, "entries": [
        {"p": p, "bytes": 0,
         "latency_us": {"rhd_rsa": 1.0, "psum": 5.0}},
        {"p": p, "bytes": 32 * 1024,
         "latency_us": {"psum": 1.0, "rhd_rsa": 5.0}},
    ]}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "table.json")
        with open(path, "w") as f:
            json.dump(table, f)
        auto = AggregatorConfig(strategy="auto", selector_mode="empirical",
                                selector_table=path,
                                fusion_threshold_mb=0.02)
        ref = AggregatorConfig(strategy="psum", fusion_threshold_mb=0.02)
        fn_ov, agg = grads_fn(auto, mesh, overlap=True)
        fn_ref, _ = grads_fn(ref, mesh, overlap=False)
        g_ov, g_ref = fn_ov(params, x), fn_ref(params, x)
        chosen = set(agg.last_schedule.strategies())
        assert chosen == {"rhd_rsa", "psum"}, agg.last_schedule.to_json()
        for k in params:
            assert (np.asarray(g_ov[k]) == np.asarray(g_ref[k])).all(), \
                f"overlapped mixed schedule != psum bit-exactly at {k!r}"
    print("overlap mixed-strategy (auto) ok")


def check_overlap_train_step():
    """overlap=True through the REAL train step on the partial-auto
    (data x model) mesh: same trained params as overlap=False."""
    from repro.configs import get_spec
    from repro.data.synthetic import SyntheticText
    from repro.models import build_model
    from repro.optim import sgd
    from repro.train import TrainStepConfig, make_train_step

    mesh = make_mesh((4, 2), ("data", "model"))
    spec = get_spec("smollm-360m").reduced()
    model = build_model(spec)
    data = SyntheticText(spec.vocab_size, batch=8, seq_len=16)
    finals = {}
    for overlap in (False, True):
        opt = sgd(1e-2)
        cfg = TrainStepConfig(
            aggregator=AggregatorConfig(strategy="rhd_rsa",
                                        fusion_threshold_mb=0.25,
                                        overlap=overlap),
            dp_axes=("data",))
        step_fn, sh = make_train_step(model, opt, mesh, cfg,
                                      data.batch_at(0), donate=False)
        params = model.init(jax.random.PRNGKey(1))
        state = opt.init(params)
        losses = []
        for i in range(6):
            params, state, m = step_fn(params, state, data.batch_at(i))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        assert sh["aggregator"].last_schedule.n_buckets >= 2
        finals[overlap] = params
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(finals[False]),
            jax.tree_util.tree_leaves_with_path(finals[True])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-7,
            err_msg=f"overlap diverged from post-backward at {ka}")
    print("overlap train step ok")


def check_overlap_ir_scopes():
    """The IR path in the compiled program, on a REAL executed p=8
    overlapped auto step (DESIGN.md §3.11): every IR bucket/stage path
    is a scope of the HLO's op names, inside the backward (the
    reductions run in the custom_vjp boundaries), each permute-kind
    stage's collective-permute bytes under its scope are its
    ``wire_bytes`` and together they are every permute the program
    holds; the measured replay probe lands inside the residual band,
    and the exported trace is Perfetto-loadable."""
    import re

    from repro import telemetry
    from repro.launch import hlo_analysis as H
    from repro.telemetry import closure, trace as trace_mod

    p = 8
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
    params = int_params(p)
    x = jnp.arange(p * 4, dtype=jnp.float32)
    tracer = telemetry.configure(trace_mod.TelemetryConfig(enabled=True))
    try:
        cfg = AggregatorConfig(strategy="auto", fusion_threshold_mb=0.02)
        fn, agg = grads_fn(cfg, mesh, overlap=True)
        compiled = fn.lower(params, x).compile()
        g = compiled(params, x)            # really executed, synced
        jax.block_until_ready(g)
        sched = agg.last_schedule
        text = compiled.as_text()

        op_names = re.findall(r'op_name="([^"]*)"', text)

        def scoped(scope):
            """The op names under ``aggregate/<scope>``, as written."""
            want = "/aggregate/" + scope + "/"
            return [n for n in op_names
                    if want in "/" + H.scope_path(n) + "/"]

        for bucket in sched.buckets:
            found = scoped(bucket.path)
            assert found, f"no scope for IR bucket {bucket.path}"
            assert all("transpose(" in n for n in found), \
                f"IR bucket {bucket.path} outside the backward"
        perm_sum = 0
        for path, _bucket, st in sched.iter_stages():
            scope = path.replace(".", "/")
            assert scoped(scope), f"no scope for IR stage {path}"
            got = H.scope_collective_bytes(text, "aggregate/" + scope)
            if st.hlo_kind == "collective-permute":
                assert got == st.wire_bytes, \
                    f"{path}: {got} permute bytes under its scope, " \
                    f"{st.wire_bytes} scheduled"
            perm_sum += got
        charged = H.analyze(text).collective_bytes.get(
            "collective-permute", 0)
        assert perm_sum == charged, \
            f"scoped permute bytes {perm_sum} != HLO-charged {charged}"

        # measured replay of the executed schedule: residuals in band
        measured = closure.measure_schedule(sched, reps=2, tracer=tracer)
        rep = closure.closure_report(sched, measured)
        assert rep["n_gated"] >= 1, rep     # the w bucket is gated
        assert rep["all_within_band"], [
            (r["path"], r["ratio"]) for r in rep["stages"] if r["gated"]]

        # exported trace round-trips and is trace_event-shaped
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "trace.json")
            tracer.write(path)
            with open(path) as f:
                doc = json.load(f)
            assert doc["traceEvents"], "empty Perfetto trace"
            assert all(ev["ph"] == "X" for ev in doc["traceEvents"])
            assert trace_mod.from_json(doc["repro"])
    finally:
        telemetry.configure(trace_mod.TelemetryConfig(enabled=False))
    print(f"overlap IR scopes ok (permute bytes {perm_sum} == "
          f"{charged}; probe max_ratio {rep['max_ratio']:.2f})")


def check_global_grad_norm():
    """The clip fix (ISSUE 3 satellite): clipping runs on AGGREGATED
    grads, so the norm every rank computes is the global-batch gradient
    norm — identical across ranks and equal to what a single process
    would compute on the full batch."""
    p = 8
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))

    def loss(params, x):
        # non-uniform per-rank grads: rank r sees x shard with
        # different values, grads = f(local batch)
        h = jnp.tanh(x @ params["w"])
        return jnp.mean(jnp.sum(h * h, axis=-1)) \
            + jnp.sum(params["b"] * jnp.mean(x))

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
              "b": jnp.ones((4,), jnp.float32)}
    x = jax.random.normal(jax.random.PRNGKey(1), (p * 2, 16))

    agg = GradientAggregator(
        AggregatorConfig(strategy="rhd_rsa", fusion_threshold_mb=0.01),
        ("data",), cache=PlanCache())

    def local(params, x):
        g = jax.grad(loss)(params, x)
        g = agg(g)
        g, norm = clip_by_global_norm(g, 1.0)
        # one norm value PER RANK so the runner can compare them
        return g, norm[None]

    fn = jax.jit(shard_map(local, mesh, in_specs=(P(), P("data")),
                           out_specs=(P(), P("data")),
                           axis_names={"data"}, check_vma=False))
    g, norms = fn(params, x)
    norms = np.asarray(norms)
    assert norms.shape == (p,)
    assert (norms == norms[0]).all(), \
        f"ranks disagree on the global norm: {norms}"

    # synchronous-SGD reference: mean gradient over the FULL batch in
    # one process (grad of the mean loss == mean of per-shard grads for
    # equal shard sizes)
    g_ref = jax.grad(loss)(params, x)
    ref = float(global_norm(g_ref))
    np.testing.assert_allclose(norms[0], ref, rtol=1e-5,
                               err_msg="per-rank norm != global-batch norm")

    # and the clipped gradients themselves match the sync-SGD update
    # (out_specs P() for grads: the aggregated tree is rank-replicated)
    scale = min(1.0, 1.0 / max(ref, 1e-9))
    for k in params:
        got = np.asarray(g[k], np.float32)
        want = np.asarray(g_ref[k], np.float32) * scale
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"clipped grad mismatch at {k}")
    print("global grad norm ok")


def check_train_step_norm_matches_single_process():
    """End-to-end: the train step's grad_norm metric equals the global
    norm a single process computes on the full batch."""
    from repro.configs import get_spec
    from repro.data.synthetic import SyntheticText
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train import TrainStepConfig, make_train_step

    mesh = make_mesh((8,), ("data",))
    spec = get_spec("smollm-360m").reduced()
    model = build_model(spec)
    data = SyntheticText(spec.vocab_size, batch=8, seq_len=16)
    opt = adamw(1e-3)
    cfg = TrainStepConfig(
        aggregator=AggregatorConfig(strategy="rhd_rsa",
                                    fusion_threshold_mb=0.25),
        dp_axes=("data",))
    step_fn, _ = make_train_step(model, opt, mesh, cfg, data.batch_at(0),
                                 donate=False)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    batch = data.batch_at(0)
    _, _, metrics = step_fn(params, state, batch)

    (_, _), g_ref = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch)
    ref = float(global_norm(g_ref))
    np.testing.assert_allclose(float(metrics["grad_norm"]), ref,
                               rtol=2e-4,
                               err_msg="train-step grad_norm is not the "
                                       "global-batch norm")
    print(f"train-step global norm ok ({ref:.4f})")


if __name__ == "__main__":
    check_overlap_bitexact()
    check_overlap_mixed_strategies()
    check_overlap_train_step()
    check_overlap_ir_scopes()
    check_global_grad_norm()
    check_train_step_norm_matches_single_process()
    print("ALL OVERLAP CHECKS PASSED")
