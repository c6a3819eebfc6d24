"""GradientAggregator — the paper's technique as a composable module.

Stacks the three pieces of the contribution:

    fusion (C4)  ∘  reduction algorithm (C1/C2)  ∘  plan cache (C3)

and applies them to a gradient pytree *inside* a ``shard_map`` whose data
axes are manual. The aggregator returns the MEAN gradient over all data
shards (the semantics data-parallel training expects).

Resolution goes through ONE path (DESIGN.md §3.8): :meth:`resolve`
produces a :class:`repro.core.schedule.ReduceSchedule` — the frozen IR
carrying every bucket's leaf layout, wire bytes, readiness rank and
per-axis decomposition tree — and both execution paths, the overlap
timeline, the dryrun records and the roofline wire check consume that
same object.  Execution is stage-by-stage
(:func:`repro.core.reducers.execute_stages`), so a composed two-level
schedule is just another stage list: per-LEVEL algorithm choice on
multi-axis meshes and overlap × hierarchical compose for free.

Precision policy: reductions accumulate in ``accum_dtype`` (default
float32) regardless of the gradient dtype — the TPU analogue of the
paper's "do the reduction on the accelerator with full fidelity" (their
CUDA kernels reduce in the buffer's native precision on-device instead of
staging through host memory; on TPU the equivalent fidelity concern is
bf16 gradient summation over 512 shards, so we upcast).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import telemetry

from . import codec as codec_mod
from . import compat, reducers, schedule as schedule_mod, \
    selector as selector_mod
from .compat import axis_size
from .plan_cache import GLOBAL_EXECUTOR_CACHE, GLOBAL_PLAN_CACHE, PlanCache
from .schedule import ReduceSchedule

# The named scope over the aggregator's ops in a compiled step (both
# placements); the benchmark's device-trace reduction reads it.
SCOPE = "aggregate"


def _chunk_axis(group, ndim: int) -> int:
    """First unsharded dim of a leaf whose fusion-group tag is its
    tuple-ized PartitionSpec (None entries = unsharded)."""
    if not isinstance(group, tuple) or ndim == 0:
        return 0
    for i in range(ndim):
        if i >= len(group) or group[i] is None:
            return i
    return 0


@dataclasses.dataclass(frozen=True)
class AggregatorConfig:
    strategy: str = "rhd_rsa"          # reducers.STRATEGIES, a composed
                                       # two-level name ("ring_rsa×rhd_rsa",
                                       # core/schedule.py), or "auto":
                                       # per-bucket (and per-level)
                                       # message-size-aware selection
                                       # (core/selector.py, DESIGN.md §3.5)
    fuse: bool = True                  # Horovod Tensor Fusion on/off
    fusion_threshold_mb: float = 4.0   # Horovod default threshold = 64MB;
                                       # tuned per-platform like the paper
    accum_dtype: str = "float32"
    sharding_aware: bool = True        # bucket by sharding group (beyond-paper)
    wire_dtype: str = ""               # "" = reduce in accum_dtype; e.g.
                                       # "bfloat16" halves wire bytes at a
                                       # summation-precision cost (§Perf C2)
    # -- strategy="auto" knobs ----------------------------------------------
    selector_mode: str = "analytic"    # "analytic" | "empirical"
    selector_table: str = ""           # empirical mode: path to a tuning
                                       # table JSON (allreduce_micro
                                       # --emit-table / BENCH_allreduce.json)
    selector_link: str = "ici"         # analytic mode link profile
                                       # (cost_model.LINK_PROFILES)
    align_buckets: bool = True         # align fusion boundaries to the
                                       # selector's algorithm switch points
    overlap: bool = False              # issue per-bucket reductions INSIDE
                                       # the backward (wait-free backprop,
                                       # core/overlap.py / DESIGN.md §3.6)
                                       # via overlap_params; __call__ is
                                       # the post-backward path
    # -- wire codecs (core/codec.py, DESIGN.md §3.10) -----------------------
    codec: str = "none"                # per-hop wire codec spec: a codec
                                       # name (none|bf16|int8|fp8_e4m3) or
                                       # "<inner>×<outer>" per schedule level
    error_feedback: bool = False       # keep a per-bucket residual of the
                                       # quantization error and fold it into
                                       # the next step (init_residuals /
                                       # __call__(..., residuals=...));
                                       # post-backward path only
    # -- fused hop kernels (kernels/fused_hop.py, DESIGN.md §3.13) ----------
    fused_hops: "bool | None" = None   # route codec'd hops + terminal
                                       # reductions through the Pallas
                                       # decode→accumulate→encode kernel.
                                       # None (default) = fuse exactly the
                                       # coded schedules (schedule.plan's
                                       # resolution); True/False force it

    @property
    def threshold_bytes(self) -> int:
        return int(self.fusion_threshold_mb * 2 ** 20)

    @property
    def placement(self) -> str:
        return "in_backward" if self.overlap else "post_backward"

    def validate(self):
        if self.strategy != "auto" \
                and not schedule_mod.is_strategy(self.strategy):
            raise ValueError(
                f"strategy {self.strategy!r} not in "
                f"{reducers.STRATEGIES + ('auto',)} and not a composed "
                f"'<inner>{schedule_mod.SEP}<outer>' schedule name")
        if self.selector_mode not in selector_mod.MODES:
            raise ValueError(
                f"selector_mode {self.selector_mode!r} not in "
                f"{selector_mod.MODES}")
        if self.strategy == "auto" and self.selector_mode == "empirical" \
                and not self.selector_table:
            raise ValueError("strategy='auto' with selector_mode="
                             "'empirical' needs selector_table=<json path>")
        if self.selector_link not in selector_mod.LINK_PROFILES:
            raise ValueError(
                f"selector_link {self.selector_link!r} not in "
                f"{sorted(selector_mod.LINK_PROFILES)}")
        codec_mod.validate_spec(self.codec or "none")
        if self.error_feedback:
            if (self.codec or "none") == "none":
                raise ValueError("error_feedback=True requires a wire "
                                 "codec (codec != 'none')")
            if self.overlap:
                # EF residual state is carried by the caller across
                # steps; the in-backward custom_vjp path has nowhere to
                # return the new residuals from.
                raise ValueError("error_feedback is incompatible with "
                                 "overlap=True (post-backward path only)")

    def resolve_fused_hops(self) -> bool:
        """The fused-hop default of ``schedule.plan``: ``None`` means
        coded schedules fuse, uncoded schedules stay on plain XLA."""
        if self.fused_hops is None:
            return (self.codec or "none") != "none"
        return bool(self.fused_hops)

    def make_selector(self) -> "selector_mod.Selector | None":
        if self.strategy != "auto":
            return None
        wire = jnp.dtype(self.wire_dtype or self.accum_dtype)
        return selector_mod.make_selector(
            self.selector_mode, table=self.selector_table or None,
            link=self.selector_link, codec=self.codec or "none",
            wire_itemsize=wire.itemsize,
            fused=self.resolve_fused_hops())


class GradientAggregator:
    """Aggregates gradient pytrees over manual data axes.

    Parameters
    ----------
    config: AggregatorConfig
    dp_axes: manual mesh axis names, outermost first — e.g. ``("data",)``
        or ``("pod", "data")`` for the multi-pod mesh.
    cache: PlanCache (defaults to the process-global one).
    model_axis: the manual tensor-parallel axis of the full-manual train
        step (DESIGN.md §3.12), or None.  When set, gradients arrive
        shard-shaped for model-sharded leaves (the gather boundary in
        core/manual.py slices their cotangents) and replicated-group
        buckets get the model BRACKET — dp stages on a 1/m chunk plus a
        terminal ``ag@model`` — so no dp reduction is duplicated across
        model ranks.  The reduction itself still averages over the data
        axes only.
    """

    def __init__(self, config: AggregatorConfig,
                 dp_axes: Sequence[str],
                 cache: PlanCache | None = None,
                 model_axis: "str | None" = None):
        config.validate()
        self.config = config
        self.dp_axes = tuple(dp_axes)
        self.model_axis = model_axis
        self.cache = cache if cache is not None else GLOBAL_PLAN_CACHE
        self.selector = config.make_selector()
        # The ReduceSchedule resolved by the last resolve() /
        # __call__ / overlap_params — EVERY path records the same IR
        # (preview and execution can never disagree; the old split
        # last_schedule/last_plan pair could go stale when a preview
        # preceded a real call with different grads).
        self.last_schedule: ReduceSchedule | None = None

    # -- resolution (the single path) ---------------------------------------

    def _wire_dtype(self) -> str:
        cfg = self.config
        return str(jnp.dtype(cfg.wire_dtype or cfg.accum_dtype))

    def resolve(self, grads, axis_sizes: Sequence[int],
                groups=None,
                model_axis_size: "int | None" = None) -> ReduceSchedule:
        """Resolve ``grads`` (arrays or ShapeDtypeStructs) into the
        :class:`ReduceSchedule` IR without running a reduction.

        ``axis_sizes`` are the data-axis sizes (outermost first,
        matching ``dp_axes``) — passed explicitly because this also
        runs outside ``shard_map`` (launch/dryrun's preview path).
        The same call happens at trace time inside ``__call__`` /
        ``overlap_params``, so the preview IS the executed schedule.

        ``model_axis_size`` must be given (same reason) when the
        aggregator carries a ``model_axis``; preview callers pass the
        mesh's model-axis size and SHARD-shaped grad structs
        (core/manual.py ``shard_param_structs``) so the previewed
        schedule is the traced one.
        """
        cfg = self.config
        if not cfg.sharding_aware:
            groups = None
        if self.model_axis is not None and model_axis_size is None:
            raise ValueError(
                f"aggregator has model_axis={self.model_axis!r}; resolve "
                f"needs its size (static inside the trace, explicit in "
                f"preview calls)")
        sched = schedule_mod.plan(
            grads, axis_names=self.dp_axes,
            axis_sizes=tuple(int(s) for s in axis_sizes),
            strategy=cfg.strategy if cfg.strategy != "auto" else "rhd_rsa",
            selector=self.selector,
            threshold_bytes=cfg.threshold_bytes, fuse=cfg.fuse,
            groups=groups, wire_dtype=self._wire_dtype(),
            align_buckets=cfg.align_buckets, placement=cfg.placement,
            intra=cfg.selector_link, inter="dcn",
            codec=cfg.codec or "none",
            error_feedback=cfg.error_feedback,
            fused_hops=cfg.fused_hops,
            model_axis=self.model_axis,
            model_axis_size=int(model_axis_size or 1), cache=self.cache)
        self.last_schedule = sched
        if telemetry.enabled():
            telemetry.metrics.record_schedule(sched)
            telemetry.record_plan_cache(self.cache)
            telemetry.record_executor_cache(GLOBAL_EXECUTOR_CACHE)
        return sched

    def _trace_context(self, grads, groups):
        """(schedule, scale) resolved at shard_map trace time — shared
        by the post-backward and in-backward paths.  Mesh axis sizes
        are static inside the trace, so the whole schedule (fusion
        layout, per-bucket strategy, per-axis stages) is resolved at
        trace time and the compiled step hard-codes it."""
        axis_sizes = tuple(axis_size(ax) for ax in self.dp_axes)
        msize = axis_size(self.model_axis) \
            if self.model_axis is not None else None
        sched = self.resolve(grads, axis_sizes, groups=groups,
                             model_axis_size=msize)
        dp_size = 1
        for s in axis_sizes:
            dp_size *= s
        return sched, 1.0 / dp_size

    # -- execution ----------------------------------------------------------

    def _reduce_buffer(self, bucket: "schedule_mod.BucketSchedule",
                       group, buf, scale, residual=None):
        """Reduce ONE bucket's fused buffer: cast to the wire/accum
        dtype, run the bucket's decomposition tree stage-by-stage,
        apply the mean scale, cast back.

        ``residual`` enables error feedback: the bucket sends
        ``q(g + r)`` instead of ``g`` through the codec'd stages and the
        new residual ``(g + r) - q(g + r)`` is returned alongside the
        reduced buffer (the caller threads it to the next step).  EF
        quantizes ONCE on the whole fused buffer before the stage walk —
        the per-hop codec then transports an already-on-grid payload."""
        cfg = self.config
        # The bucket's IR path as a scope: its ops carry
        # ``.../bucket[i]/stage[j]/hop[k]`` in the compiled program.
        with jax.named_scope(bucket.path):
            accum = jnp.dtype(cfg.wire_dtype or cfg.accum_dtype)
            orig = buf.dtype
            new_residual = None
            if residual is not None:
                cname = next((st.codec for st in bucket.stages
                              if st.codec != "none"), "none")
                if cname != "none":
                    buf, new_residual = codec_mod.ef_quantize(
                        cname, buf, residual)
                    buf = buf.astype(orig)
                else:
                    # Bucket ended up uncoded (e.g. psum won the argmin):
                    # nothing was quantized, so nothing feeds back.
                    new_residual = residual
            if orig != accum:
                buf = buf.astype(accum)
            # chunked reducers slice along dim 0; if the bucket's leaf is
            # model-sharded on dim 0, rotate an unsharded dim to the front
            # so the auto sharding is never disturbed (§Perf it.0).
            axis = _chunk_axis(group, buf.ndim)
            if axis != 0:
                buf = jnp.moveaxis(buf, axis, 0)
            buf = reducers.execute_stages(buf, bucket.stages)
            if axis != 0:
                buf = jnp.moveaxis(buf, 0, axis)
            out = (buf * scale).astype(orig)
        if residual is not None:
            return out, new_residual
        return out

    def init_residuals(self, grads, groups=None):
        """Zero error-feedback state: one float32 buffer per fusion
        bucket, shaped like the fused gradient buffers ``__call__``
        reduces.  Thread the tuple through training steps:
        ``grads, res = agg(grads, residuals=res)``.  Call inside the
        same shard_map context as :meth:`__call__` (the fused layout
        depends on the mesh axis sizes)."""
        sched, _ = self._trace_context(grads, groups)
        plan = sched.plan
        return tuple(jnp.zeros(buf.shape, jnp.float32)
                     for buf in plan.flatten(grads))

    def __call__(self, grads, groups=None, residuals=None):
        """Mean-allreduce ``grads`` over the data axes (post-backward
        path: one aggregation block after ``value_and_grad``).

        ``groups``: optional pytree of sharding-group tags matching
        ``grads`` (from the model's parameter sharding rules); only used
        when ``config.sharding_aware`` to keep fused buffers from crossing
        auto-axis sharding classes.

        ``residuals``: error-feedback state from :meth:`init_residuals`
        (or a previous call); when given, returns
        ``(reduced_grads, new_residuals)``.
        """
        sched, scale = self._trace_context(grads, groups)
        plan = sched.plan
        reduced = []
        new_residuals = []
        # Packing into the fused buffers and unpacking are the
        # aggregator's own work: both sit inside its scope.
        with jax.named_scope(SCOPE):
            bufs = plan.flatten(grads)
            if residuals is not None and len(residuals) != len(bufs):
                raise ValueError(
                    f"{len(residuals)} residual buffers for "
                    f"{len(bufs)} fusion buckets — pass init_residuals() "
                    f"output for these grads")
            for i, (bucket, buf) in enumerate(zip(sched.buckets, bufs)):
                group = plan.buckets[bucket.index].group
                if residuals is not None:
                    out, r = self._reduce_buffer(bucket, group, buf, scale,
                                                 residual=residuals[i])
                    new_residuals.append(r)
                else:
                    out = self._reduce_buffer(bucket, group, buf, scale)
                reduced.append(out)
            grads = plan.unflatten(reduced)
        if residuals is not None:
            return grads, tuple(new_residuals)
        return grads

    # -- overlapped (in-backward) path --------------------------------------

    def _bucket_boundary(self, sched, bucket, scale):
        """Identity on the bucket's param leaves whose VJP mean-reduces
        the cotangents — the reduction lands INSIDE the backward, gated
        only on this bucket's own gradients."""
        plan = sched.plan
        group = plan.buckets[bucket.index].group

        @jax.custom_vjp
        def boundary(*leaves):
            return leaves

        def fwd(*leaves):
            return leaves, None

        def bwd(_, cts):
            with jax.named_scope(SCOPE):
                buf = plan.flatten_bucket(plan.buckets[bucket.index],
                                          list(cts))
                buf = self._reduce_buffer(bucket, group, buf, scale)
                return tuple(plan.unflatten_bucket(
                    plan.buckets[bucket.index], buf))

        boundary.defvjp(fwd, bwd)
        return boundary

    def overlap_params(self, params, groups=None):
        """Stage per-bucket reductions inside the backward pass.

        Returns ``params`` unchanged in value, but every fusion bucket's
        leaves pass through a ``jax.custom_vjp`` boundary whose backward
        rule mean-allreduces that bucket's cotangents (the Horovod
        wait-free-backprop analogue, DESIGN.md §3.6): each collective
        depends only on its own bucket's gradients, so XLA is free to
        interleave it with the remaining backward compute instead of
        emitting one trailing collective block.

        Call INSIDE the function being differentiated; the gradients
        that come out of ``value_and_grad`` are then already aggregated
        — do not also pass them through :meth:`__call__`.  Buckets are
        wrapped in the IR's readiness order (last layer's bucket
        first), matching the order their reductions can launch — this
        works for ANY stage list, so overlap composes with the
        two-level schedules.
        """
        sched, scale = self._trace_context(params, groups)
        flat, treedef = jax.tree_util.tree_flatten(params)
        out = list(flat)
        for bi in sched.readiness_order():
            bucket = sched.buckets[bi]
            boundary = self._bucket_boundary(sched, bucket, scale)
            wrapped = boundary(*[flat[i] for i in bucket.leaf_indices])
            for i, leaf in zip(bucket.leaf_indices, wrapped):
                out[i] = leaf
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- scalars (loss/metrics) ---------------------------------------------

    def mean_scalar(self, x):
        dp_size = 1
        for ax in self.dp_axes:
            dp_size *= axis_size(ax)
        return compat.psum(x, self.dp_axes) / dp_size
