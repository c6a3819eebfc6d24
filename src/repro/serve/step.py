"""Jitted serving steps: prefill (prompt -> cache) and decode (1 token).

Serving has no gradient aggregation, but it rides the same full-manual
lowering as training (DESIGN.md §3.12) when the mesh carries a ``model``
axis: parameters enter the region shard-shaped under the per-leaf specs
of :func:`repro.core.manual.model_shard_specs` and the gather boundary
reconstructs them before the forward — real tensor-parallel parameter
sharding with every mesh axis manual.  The KV cache stays REPLICATED
over the model axis inside the manual region (the gathered forward
computes full per-layer tensors on every model rank); batch/tokens/
logits shard over the data axes.  Meshes without a model axis — or
``seq_parallel`` specs, whose residual-stream constraint only GSPMD can
express — keep the plain GSPMD jit.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import manual as manual_mod
from repro.core.compat import shard_map
from repro.data.synthetic import batch_pspecs
from repro.models import ModelApi, param_pspecs
from .sharding import cache_pspecs


def sanitize_pspec(spec: P, mesh) -> P:
    """Drop axis names the mesh doesn't have (e.g. running a model-
    parallel-ruled model on a data-only host mesh)."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in tuple(spec)))


def strip_axis(spec: P, axis: str = "model") -> P:
    """The spec with every ``axis`` entry removed (replicated over it).
    The manual serving region keeps caches model-replicated: the
    gathered forward produces identical full tensors on every model
    rank, so a model-sharded cache would demand a scatter the region
    never performs."""
    def keep(entry):
        if entry == axis:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e != axis)
            return kept if kept else None
        return entry

    return P(*(keep(e) for e in tuple(spec)))


def _ns(mesh, tree):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, sanitize_pspec(spec, mesh)), tree,
        is_leaf=lambda x: isinstance(x, P))


def _manual_serve(model: ModelApi, mesh) -> bool:
    """Take the full-manual tensor-parallel path?  Mirrors the train
    step's gate: a real model axis, and no GSPMD-only sequence
    parallelism."""
    return (int(mesh.shape.get("model", 1)) > 1
            and not bool(getattr(model.spec, "seq_parallel", False)))


def make_prefill_step(model: ModelApi, mesh, dp_axes, batch_example,
                      max_seq: int):
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_pspecs(params_struct)
    bspecs = batch_pspecs(batch_example, dp_axes)

    b = jax.tree_util.tree_leaves(batch_example)[0].shape[0]
    cache_tpl = jax.eval_shape(lambda: model.init_cache(b, max_seq))
    cspecs = cache_pspecs(cache_tpl, mesh, dp_axes)

    if _manual_serve(model, mesh):
        mspecs = manual_mod.model_shard_specs(params_struct, mesh)
        cspecs = jax.tree_util.tree_map(strip_axis, cspecs,
                                        is_leaf=lambda x: isinstance(x, P))
        dp_size = 1
        for ax in dp_axes:
            dp_size *= mesh.shape[ax]
        logit_spec = P(tuple(dp_axes), None) \
            if dp_size > 1 and b % dp_size == 0 else P(None, None)

        def fn(params, batch):
            return model.prefill(manual_mod.gather_params(params, mspecs),
                                 batch, max_seq)

        smapped = shard_map(fn, mesh,
                            in_specs=(mspecs, bspecs),
                            out_specs=(logit_spec, cspecs),
                            axis_names=None, check_vma=False)
        return jax.jit(smapped,
                       in_shardings=(_ns(mesh, mspecs), _ns(mesh, bspecs)),
                       out_shardings=(NamedSharding(
                           mesh, sanitize_pspec(logit_spec, mesh)),
                           _ns(mesh, cspecs)))

    def fn(params, batch):
        return model.prefill(params, batch, max_seq)

    return jax.jit(fn,
                   in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)),
                   out_shardings=(None, _ns(mesh, cspecs)))


def make_decode_step(model: ModelApi, mesh, dp_axes, batch: int,
                     max_seq: int, donate: bool = True):
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_pspecs(params_struct)
    cache_tpl = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    cspecs = cache_pspecs(cache_tpl, mesh, dp_axes)
    dp_size = 1
    for ax in dp_axes:
        dp_size *= mesh.shape[ax]
    tok_spec = P(tuple(dp_axes), None) if batch % dp_size == 0 and \
        dp_size > 1 else P(None, None)

    if _manual_serve(model, mesh):
        mspecs = manual_mod.model_shard_specs(params_struct, mesh)
        cspecs = jax.tree_util.tree_map(strip_axis, cspecs,
                                        is_leaf=lambda x: isinstance(x, P))
        logit_spec = tok_spec

        def fn(params, cache, tokens):
            return model.decode_step(
                manual_mod.gather_params(params, mspecs), cache, tokens)

        smapped = shard_map(fn, mesh,
                            in_specs=(mspecs, cspecs, tok_spec),
                            out_specs=(logit_spec, cspecs),
                            axis_names=None, check_vma=False)
        return jax.jit(smapped,
                       in_shardings=(_ns(mesh, mspecs), _ns(mesh, cspecs),
                                     NamedSharding(
                                         mesh, sanitize_pspec(tok_spec,
                                                              mesh))),
                       out_shardings=(NamedSharding(
                           mesh, sanitize_pspec(logit_spec, mesh)),
                           _ns(mesh, cspecs)),
                       donate_argnums=(1,) if donate else ())

    def fn(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    return jax.jit(fn,
                   in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs),
                                 NamedSharding(mesh, tok_spec)),
                   out_shardings=(None, _ns(mesh, cspecs)),
                   donate_argnums=(1,) if donate else ())
