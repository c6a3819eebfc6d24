"""Full-manual model-axis lowering (DESIGN.md §3.12).

The train/serve steps make the ``model`` axis manual as well as the
data axes, so the compiled program carries exactly the collectives the
ReduceSchedule names: parameters enter the region shard-shaped
(per-leaf specs restricted to the model axis, derived from
``models.param_pspecs``) and a differentiable gather boundary
reconstructs the full tensors inside the region.

The boundary is a ``jax.custom_vjp`` per sharded leaf:

* forward — ``all_gather`` the shard along its sharded dim (m-1 hops of
  the shard bytes on the innermost link; charged to the HLO all-gather
  kind, which ``wire_check`` does not bound);
* backward — slice the cotangent back to this rank's block.  No psum:
  the batch is sharded over the data axes only, so every model rank
  computes the loss from identical (batch-shard, full-params) inputs and
  the cotangents are already replicated across the model axis — a psum
  here would overcount by the model-axis size.

Gradients therefore leave the region shard-shaped for model-sharded
leaves and full-shaped for replicated leaves; the aggregator reduces
both over the data axes only, adding the three-level "model bracket"
(shard -> dp stages -> ag@model) to replicated buckets so no dp wire or
reduction work is duplicated across model ranks (core/schedule.py).

Leaves whose sharded dim does not divide the model-axis size fall back
to replicated specs per-leaf (mirroring ``models.divisibility_check``),
so the manual path never requires a divisible architecture.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import compat


MODEL_AXIS = "model"


def _entry_has(entry, axis: str) -> bool:
    if entry == axis:
        return True
    return isinstance(entry, tuple) and axis in entry


def _restrict(spec, axis: str):
    """Keep only ``axis`` entries of a PartitionSpec (replicate the rest)."""
    return P(*(axis if _entry_has(e, axis) else None for e in tuple(spec)))


def sharded_dim(spec, axis: str = MODEL_AXIS):
    """Index of the dim sharded over ``axis``, or None if replicated."""
    for i, e in enumerate(tuple(spec)):
        if _entry_has(e, axis):
            return i
    return None


def model_shard_specs(params, mesh, axis: str = MODEL_AXIS):
    """Per-leaf PartitionSpecs restricted to the model axis.

    Derived from ``models.param_pspecs``; leaves whose sharded dim does
    not divide the axis size fall back to ``P()`` (replicated).  Returns
    a pytree of specs usable both as shard_map in/out_specs and (via
    NamedSharding) as jit in/out_shardings.
    """
    from ..models import param_pspecs

    m = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    specs = param_pspecs(params)

    def leaf_spec(leaf, spec):
        spec = _restrict(spec, axis)
        dim = sharded_dim(spec, axis)
        if dim is None:
            return P()
        if m <= 1 or leaf.shape[dim] % m != 0:
            return P()
        return spec

    return jax.tree_util.tree_map(leaf_spec, params, specs)


def shard_param_structs(params, mspecs, m: int, axis: str = MODEL_AXIS):
    """ShapeDtypeStruct tree with model-sharded dims divided by ``m`` —
    the shapes gradients take inside the full-manual region.  Used by the
    dry-run preview so its resolved schedule matches the traced one."""

    def shrink(leaf, spec):
        dim = sharded_dim(spec, axis)
        shape = tuple(leaf.shape)
        if dim is not None and m > 1:
            shape = shape[:dim] + (shape[dim] // m,) + shape[dim + 1:]
        return jax.ShapeDtypeStruct(shape, leaf.dtype)

    return jax.tree_util.tree_map(shrink, params, mspecs)


def sharded_mask(params, mspecs, axis: str = MODEL_AXIS):
    """Pytree of bools: True where the leaf is model-sharded (its squared
    norm must be psum'd over the model axis, optim/clip.py)."""
    return jax.tree_util.tree_map(
        lambda _, spec: sharded_dim(spec, axis) is not None, params, mspecs)


def _gather_leaf(x, dim: int, axis: str):
    """Differentiable all-gather of one shard along ``dim`` (docstring)."""
    m = compat.axis_size(axis)
    if m == 1:
        return x
    shard = x.shape[dim]

    def _ag(v):
        stacked = compat.all_gather(v, axis)          # (m,) + v.shape
        full = jnp.moveaxis(stacked, 0, dim)          # blocks at dim
        shape = v.shape[:dim] + (shard * m,) + v.shape[dim + 1:]
        return full.reshape(shape)

    @jax.custom_vjp
    def gather(v):
        return _ag(v)

    def fwd(v):
        return _ag(v), None

    def bwd(_, ct):
        idx = compat.axis_index(axis)
        return (jax.lax.dynamic_slice_in_dim(ct, idx * shard, shard,
                                             axis=dim),)

    gather.defvjp(fwd, bwd)
    return gather(x)


def gather_params(params, mspecs, axis: str = MODEL_AXIS):
    """Reconstruct full parameters from model shards inside a full-manual
    region.  Leaves with replicated specs pass through untouched."""

    def leaf(x, spec):
        dim = sharded_dim(spec, axis)
        return x if dim is None else _gather_leaf(x, dim, axis)

    return jax.tree_util.tree_map(leaf, params, mspecs)
