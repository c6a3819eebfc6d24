"""The distributed train step — where the paper's technique plugs in.

Structure (DESIGN.md §3.1, §3.6):

    jax.jit( jax.shard_map(step, axis_names={pod, data}) )
                │
                ├─ value_and_grad(model.loss)    # local data shard;
                │     └─ overlap=True: per-bucket reductions issued
                │        INSIDE the backward (aggregator.overlap_params)
                ├─ GradientAggregator(...)       # overlap=False: one
                │                                #   post-backward block
                ├─ clip_by_global_norm           # on AGGREGATED grads —
                │                                #   the TRUE global norm,
                │                                #   identical on every
                │                                #   rank (sync-SGD
                │                                #   semantics)
                └─ optimizer.update + apply      # replicated over data,
                                                 #   model-sharded via auto

Named scopes (``jax.named_scope``, HLO metadata only): the aggregator's
ops sit under ``aggregate``, clipping under ``clip``, the optimizer and
the parameter add under ``optimizer``, and the model's layers under
their own names (``embed``, ``attention``/``sdpa``, ``mlp``, ``norm``,
``head``); JAX marks the forward ``jvp(``, the backward
``transpose(jvp(`` and remat's recompute ``rematted_computation``.  A
profiler's device trace times each op, and its ``op_name`` says which
of these it belongs to.

The data axes are MANUAL: the gradient sum over data shards happens only
through the aggregator's explicit algorithm (the compiled HLO contains
our collective-permutes, no XLA-chosen allreduce).  The ``model`` axis
is manual too (full-manual lowering, DESIGN.md §3.12): parameters enter
the region SHARD-shaped under per-leaf specs derived from
``param_pspecs`` (core/manual.py), a differentiable gather boundary
reconstructs the full tensors for the loss, and its backward slices each
cotangent back to the rank's shard — so model-sharded leaves dp-reduce
at 1/m wire while replicated buckets carry the IR's three-level model
bracket (``ring@data×rhd@pod×ag@model``).  The pre-§3.12 partial-auto
lowering (model axis AUTO under GSPMD) survives as the explicit
``legacy_partial_auto`` opt-in, which ``seq_parallel`` residual
sharding requires, since only GSPMD can express it.

Clipping order matters twice.  The seed clipped LOCAL grads by each
rank's own shard norm before aggregation, which (a) is not synchronous
SGD — every rank scaled by a different norm and the reported
``grad_norm`` was rank-local — and (b) made every collective's input
depend on EVERY gradient leaf through the norm scalar, serializing the
whole schedule into one trailing block.  Clipping the aggregated mean
gradient fixes the semantics and removes the barrier that would defeat
the overlap path (pinned by tests/test_overlap_hlo.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import AggregatorConfig, GradientAggregator
from repro.core import manual as manual_mod
from repro.core.compat import shard_map
from repro.data.synthetic import batch_pspecs
from repro.models import ModelApi, param_groups, param_pspecs
from repro.optim import Optimizer, clip_by_global_norm


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    aggregator: AggregatorConfig = AggregatorConfig()
    clip_norm: float = 1.0
    dp_axes: tuple = ("data",)


def make_train_step(model: ModelApi, optimizer: Optimizer,
                    mesh, cfg: TrainStepConfig,
                    batch_example: Any,
                    donate: bool = True,
                    legacy_partial_auto: bool = False):
    """Build the jitted multi-device train step.

    ``batch_example``: pytree of arrays or ShapeDtypeStructs with GLOBAL
    shapes (leading dim = global batch).
    Returns (step_fn, shardings) where
    ``step_fn(params, opt_state, batch) -> (params, opt_state, metrics)``.

    ``legacy_partial_auto``: lower the model axis AUTO under GSPMD (a
    partial-auto ``shard_map`` with only the data axes manual) instead
    of the default full-manual region.  ``seq_parallel`` specs force
    this path since their residual-stream sharding constraint is a
    GSPMD annotation the manual region cannot express.
    """
    # The step's named scopes are in its op names, which a profile
    # reads.  JAX's persistent cache leaves metadata out of its key by
    # default, so a step whose scopes changed would load an executable
    # compiled from an earlier version and show that version's names.
    # Key on the metadata, and keep the callers' tracebacks out of it,
    # so that one program compiled from two call sites is one entry.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    dp_axes = tuple(cfg.dp_axes)
    model_axis = "model" if "model" in mesh.axis_names else None
    seq_parallel = bool(getattr(model.spec, "seq_parallel", False))
    manual = (model_axis is not None and not legacy_partial_auto
              and not seq_parallel)

    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_pspecs(params_struct)
    sspecs = optimizer.state_pspecs(pspecs)

    mspecs = sharded_mask = None
    if manual:
        mspecs = manual_mod.model_shard_specs(params_struct, mesh)
        sharded_mask = manual_mod.sharded_mask(params_struct, mspecs)
    agg = GradientAggregator(cfg.aggregator, dp_axes,
                             model_axis=model_axis if manual else None)

    def gather(p):
        return manual_mod.gather_params(p, mspecs) if manual else p

    def local_step(params, opt_state, batch):
        groups = param_groups(params)
        if cfg.aggregator.overlap:
            # In-backward aggregation: the boundary must sit inside the
            # differentiated function so each bucket's reduction fires
            # as its cotangents complete (readiness order).  The gather
            # boundary wraps OUTSIDE the bucket boundaries, so sharded
            # cotangents are sliced back before their bucket reduces.
            def loss_fn(p, b):
                return model.loss(
                    gather(agg.overlap_params(p, groups=groups)), b)
        else:
            def loss_fn(p, b):
                return model.loss(gather(p), b)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        if not cfg.aggregator.overlap:
            grads = agg(grads, groups=groups)           # ← the technique
        # Clip AFTER aggregation: the norm is the global-batch gradient
        # norm, identical on every rank.  On the full-manual path the
        # model-sharded leaves hold 1/m each, so their squared sums are
        # psum'd over the model axis (replicated leaves counted once);
        # on the legacy path GSPMD combines the auto-axis partial sums.
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(
                grads, cfg.clip_norm,
                sharded=sharded_mask if manual else None,
                model_axis=model_axis if manual else None)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates)
        metrics = {**metrics, "loss": loss, "grad_norm": gnorm}
        metrics = {k: agg.mean_scalar(v) for k, v in metrics.items()}
        return params, opt_state, metrics

    bspecs = batch_pspecs(batch_example, dp_axes)
    if manual:
        # Full-manual region: params/opt state enter shard-shaped under
        # the per-leaf model specs; every mesh axis is manual.
        region_pspecs: Any = mspecs
        region_sspecs: Any = optimizer.state_pspecs(mspecs)
        region_axes = None
    else:
        region_pspecs = P()
        region_sspecs = P()
        region_axes = set(dp_axes)
    smapped = shard_map(
        local_step, mesh,
        in_specs=(region_pspecs, region_sspecs, bspecs),
        out_specs=(region_pspecs, region_sspecs, P()),
        axis_names=region_axes,
        check_vma=False)

    from repro.serve.step import sanitize_pspec

    def ns(tree):
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, sanitize_pspec(spec, mesh)),
            tree, is_leaf=lambda x: isinstance(x, P))

    batch_sh = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), bspecs,
        is_leaf=lambda x: isinstance(x, P))

    if manual:
        # jit shardings must agree with the region specs exactly —
        # mismatches would insert GSPMD reshards at the region boundary.
        pspecs, sspecs = region_pspecs, region_sspecs
    jitted = jax.jit(
        smapped,
        in_shardings=(ns(pspecs), ns(sspecs), batch_sh),
        out_shardings=(ns(pspecs), ns(sspecs), None),
        donate_argnums=(0, 1) if donate else ())
    # "aggregator" rides along so callers (launch/dryrun, examples) can
    # report the resolved per-bucket schedule of strategy="auto".
    return jitted, {"params": pspecs, "opt": sspecs, "batch": bspecs,
                    "aggregator": agg}
