#!/usr/bin/env python3
"""Smoke run of the training and serving path on TPU, at full width.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # data-parallel aggregation, 4 chips

One chip, in one process:

  train   smollm-360m at its published widths (remat on) takes a few
          AdamW steps on one repeated 4 x 1024 batch through ``Trainer`` /
          ``make_train_step`` on a (data=1, model=1) mesh;
  kernels every fused-hop codec kernel, compiled by Mosaic, on a 16 MiB
          bucket against the ``core/codec.py`` composition;
  serve   ``ServeEngine.generate`` (4 prompts x 128 tokens, 16 new,
          greedy), checked against the train-path forward.

``--chips 4`` runs only the aggregation phase: one SGD step of the same
model and per-chip batch on a (data=4, model=1) mesh per strategy
(``psum`` as the reference, ``ring_rsa``, ``rhd_rsa``, and ``ring_rsa``
with the int8 codec on the fused-hop route), all from one init.

Every check raises on failure, so the script exits non-zero.  The last
line of standard output is one JSON object naming the device.  Times are
host-clock seconds around work that ends in ``block_until_ready``.
"""
import argparse
import json
import math
import os
import statistics
import sys
import time

import jax

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
sys.path.insert(0, SRC)

ARCH = "smollm-360m"
BATCH, SEQ = 4, 1024             # per chip
TRAIN_STEPS = 6
TRAIN_LR = 3e-4
# Random init gives near-uniform logits: the first loss sits near ln(V).
LOSS0_BAND = 1.0
HOP_N = 4 * 2 ** 20 + 1000       # 16 MiB of f32 plus a ragged tail
FMA_REL = 2.0 ** -20             # tests/test_fused_hop.py's decode bound
PROMPTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 16
# bf16 logits from two programs (prefill vs forward) that XLA fuses
# differently: 0.05 of the logits' absmax, about 13 bf16 ulps (2^-8).
LOGIT_REL_TOL = 0.05
AGG_LR = 1e-2
# The float32 reducers differ from psum only in summation order: a few
# ulps of each gradient, plus one rounding of p + update to the
# parameter's own ulp (2^-23 of the largest |p|).
F32_REL_TOL = 1e-5
AGG_STRATEGIES = (("psum", "none"), ("ring_rsa", "none"),
                  ("rhd_rsa", "none"), ("ring_rsa", "int8"))


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


class CompileLog:
    """Backend compile seconds and persistent-cache lookups, hits and
    writes, from JAX's monitoring events.  A lookup that neither hits nor
    writes compiled in under ``jax_persistent_cache_min_compile_time_secs``
    and is compiled again by every run."""

    def __init__(self):
        self.seconds = 0.0
        self.lookups = 0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1   # JAX records this event as it writes

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def require_tpu():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX reports {len(devs)} "
                 f"{d.platform} device(s) ({d.device_kind})")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def full_spec():
    import dataclasses

    from repro.configs import get_spec
    return dataclasses.replace(get_spec(ARCH), remat=True)


def data_mesh(n):
    from repro.core.compat import make_mesh
    return make_mesh((n, 1), ("data", "model"), devices=jax.devices()[:n])


def train_phase(spec, mesh, log, batch=BATCH, seq=SEQ, steps=TRAIN_STEPS):
    """AdamW steps on one repeated batch; returns (model, params)."""
    from repro.data.synthetic import SyntheticText
    from repro.launch.mesh import dp_axes_of
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train import Trainer, TrainerConfig, TrainStepConfig

    model = build_model(spec)
    fixed = SyntheticText(spec.vocab_size, batch=batch, seq_len=seq,
                          seed=0).batch_at(0)
    cfg = TrainerConfig(steps=steps, step=TrainStepConfig(
        dp_axes=dp_axes_of(mesh)))
    trainer = Trainer(model, adamw(TRAIN_LR), mesh, lambda _: fixed, cfg)
    params, opt_state = trainer.init_state(seed=0)
    losses, gnorms, times = [], [], []
    compile0 = log.seconds
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, m = trainer.step_fn(params, opt_state, fixed)
        jax.block_until_ready((params, opt_state, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
    compile_s = log.seconds - compile0
    steady = statistics.median(times[1:])
    print(f"train: losses {losses}", flush=True)
    print(f"train: grad norms {gnorms}", flush=True)
    print(f"train: compile {compile_s:.3f} s, first step {times[0]:.3f} s, "
          f"steady step {steady:.4f} s (median of {len(times) - 1}; "
          f"{jax.devices()[0].platform} host clock)", flush=True)
    check(all(math.isfinite(v) for v in losses + gnorms),
          f"non-finite loss or grad norm: {losses} {gnorms}")
    ln_v = math.log(spec.vocab_size)
    check(abs(losses[0] - ln_v) <= LOSS0_BAND,
          f"first loss {losses[0]} outside ln(V)={ln_v:.3f}±{LOSS0_BAND}")
    check(losses[-1] < losses[0],
          f"loss on the repeated batch did not fall: {losses}")
    del opt_state
    return model, params


def _bits(a):
    import numpy as np
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


def kernel_phase(n=HOP_N, interpret=False):
    """Fused-hop kernels vs the codec.py composition, every codec."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import codec
    from repro.kernels import fused_hop as fh

    kx, ka = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n,), jnp.float32).at[n // 3].set(300.0)
    add = jax.random.normal(ka, (n,), jnp.float32)
    for name in fh.HOP_CODECS:
        payload, scale = jax.jit(lambda v: fh.hop_encode(
            name, v, interpret=interpret))(x)
        ref_payload, ref_scale = jax.jit(lambda v: codec.encode(name, v))(x)
        check(payload.dtype == ref_payload.dtype,
              f"{name}: payload dtype {payload.dtype} != "
              f"{ref_payload.dtype}")
        check((_bits(payload) == _bits(ref_payload)).all(),
              f"{name}: kernel payload bits != codec payload bits")
        check((scale is None) == (ref_scale is None)
              and (scale is None or float(scale) == float(ref_scale)),
              f"{name}: scale {scale} != codec {ref_scale}")
        got = np.asarray(jax.jit(lambda p, s, a: fh.hop_decode_add(
            name, p, s, a, interpret=interpret))(payload, scale, add))
        ref = np.asarray(jax.jit(
            lambda p, s, a: a + codec.decode(name, p, s))(payload, scale,
                                                          add))
        diff = float(np.max(np.abs(got - ref)))
        bound = FMA_REL * max(float(np.max(np.abs(ref))), 1e-30)
        print(f"kernels: {name}: encode bit-exact, decode_add max diff "
              f"{diff:.3e} (bound {bound:.3e})", flush=True)
        check(diff <= bound, f"{name}: decode_add diff {diff} > {bound}")


def serve_phase(model, params, mesh, prompts=PROMPTS,
                prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS):
    """Greedy generation, checked against the train-path forward."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import SyntheticText
    from repro.launch.mesh import dp_axes_of
    from repro.models import transformer
    from repro.serve import ServeEngine
    from repro.serve.engine import ServeConfig
    from repro.serve.step import make_prefill_step

    spec = model.spec
    tokens = SyntheticText(spec.vocab_size, batch=prompts,
                           seq_len=prompt_len, seed=1).batch_at(0)["tokens"]
    batch = {"tokens": tokens}
    cfg = ServeConfig(max_new_tokens=new_tokens,
                      max_seq=prompt_len + new_tokens)
    engine = ServeEngine(model, params, mesh, dp_axes_of(mesh), cfg)
    t0 = time.perf_counter()
    out = engine.generate(batch)
    gen_s = time.perf_counter() - t0
    print(f"serve: generated {out.shape} in {gen_s:.3f} s incl. compile "
          f"({jax.devices()[0].platform} host clock)", flush=True)
    check(out.shape == (prompts, new_tokens), f"shape {out.shape}")
    check(((out >= 0) & (out < spec.vocab_size)).all(), "token out of range")

    prefill = make_prefill_step(model, mesh, dp_axes_of(mesh), batch,
                                cfg.max_seq)
    last, _ = prefill(params, batch)
    # Reference: the forward that model.loss runs, over the prompt and
    # the generated tokens.
    seq = jnp.concatenate([tokens, jnp.asarray(out[:, :-1])], axis=1)
    ref = jax.jit(lambda p, t: transformer.forward(p, t, spec)[0])(
        params, seq)
    ref = np.asarray(ref.astype(jnp.float32))
    last = np.asarray(jnp.asarray(last, jnp.float32))
    ref_last = ref[:, prompt_len - 1]
    tol = LOGIT_REL_TOL * max(1.0, float(np.max(np.abs(ref_last))))
    diff = float(np.max(np.abs(last - ref_last)))
    print(f"serve: prefill logits vs forward max diff {diff:.4f} "
          f"(tol {tol:.4f})", flush=True)
    check(diff <= tol, f"prefill logits differ from forward by {diff}")
    check((out[:, 0] == last.argmax(-1)).all(),
          "first generated token is not the prefill argmax")
    # Each generated token must be (near) the forward's argmax at its
    # position; ties within the bf16 tolerance may go either way.
    pos = ref[:, prompt_len - 1:prompt_len - 1 + new_tokens]
    picked = np.take_along_axis(pos, out[..., None], axis=-1)[..., 0]
    gap = float(np.max(pos.max(-1) - picked))
    print(f"serve: generated tokens within {gap:.4f} of the forward's "
          f"argmax logit", flush=True)
    check(gap <= tol, f"a generated token is {gap} below the argmax")


def aggregation_phase(spec, mesh, batch=BATCH, seq=SEQ):
    """One SGD step per strategy from one init; each vs psum."""
    import jax.numpy as jnp

    from repro.analysis.verify import codec_tolerance
    from repro.core import AggregatorConfig
    from repro.data.synthetic import SyntheticText
    from repro.launch.mesh import dp_axes_of
    from repro.models import build_model
    from repro.optim import sgd
    from repro.train import TrainStepConfig, make_train_step

    model = build_model(spec)
    chips = int(mesh.shape["data"])
    data = SyntheticText(spec.vocab_size, batch=batch * chips, seq_len=seq,
                         seed=0).batch_at(0)
    opt = sgd(AGG_LR)
    params0 = model.init(jax.random.PRNGKey(0))
    max_abs_diff = jax.jit(lambda a, b: jnp.max(jnp.stack([
        jnp.max(jnp.abs(x - y)) for x, y in
        zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))])))
    p_absmax = float(jax.jit(lambda a: jnp.max(jnp.stack([
        jnp.max(jnp.abs(x)) for x in jax.tree_util.tree_leaves(a)])))(
            params0))
    ref = ref_delta = None
    for strategy, codec in AGG_STRATEGIES:
        cfg = TrainStepConfig(aggregator=AggregatorConfig(
            strategy=strategy, codec=codec), dp_axes=dp_axes_of(mesh))
        step_fn, sh = make_train_step(model, opt, mesh, cfg, data,
                                      donate=False)
        t0 = time.perf_counter()
        params, _, m = step_fn(params0, opt.init(params0), data)
        jax.block_until_ready(params)
        first_s = time.perf_counter() - t0
        loss = float(m["loss"])
        check(math.isfinite(loss), f"{strategy}/{codec}: loss {loss}")
        sched = sh["aggregator"].last_schedule
        label = f"{strategy}+{codec}" if codec != "none" else strategy
        if ref is None:
            ref = params
            ref_delta = float(max_abs_diff(ref, params0))
            check(ref_delta > 0, "psum step left the parameters unchanged")
            print(f"aggregate: psum loss {loss:.4f}, max |update| "
                  f"{ref_delta:.3e}, first step {first_s:.3f} s incl. "
                  f"compile", flush=True)
            continue
        rel = float(max_abs_diff(params, ref)) / ref_delta
        if codec == "none":
            tol = F32_REL_TOL + 2.0 ** -23 * p_absmax / ref_delta
        else:
            tol = codec_tolerance(sched)
            check(any(st.fused_hop for b in sched.buckets
                      for st in b.stages),
                  f"{label}: schedule took no fused hop")
        print(f"aggregate: {label} loss {loss:.4f}, params vs psum "
              f"{rel:.3e} of max |update| (tol {tol:.3e}), first step "
              f"{first_s:.3f} s incl. compile", flush=True)
        check(rel <= tol, f"{label}: {rel} > tolerance {tol}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.launch.runtime import configure_compile_cache
    cache_dir = configure_compile_cache()
    log = CompileLog()
    spec = full_spec()
    if args.chips == 4:
        check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
        aggregation_phase(spec, data_mesh(4))
    else:
        mesh = data_mesh(1)
        model, params = train_phase(spec, mesh, log)
        kernel_phase()
        serve_phase(model, params, mesh)
    print(f"compile cache: {cache_dir}: {log.lookups} lookups, {log.hits} "
          f"hits, {log.writes} writes, {log.seconds:.3f} s in backend "
          f"compiles", flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
