"""The single home of the repo's manual-axis collectives and shard_map.

Every other module reaches ``jax.shard_map``, the mesh constructor and
the manual-axis ``lax`` collectives through these wrappers, so one file
states how the repo uses them; the compat lint (CL001/CL002,
``analysis/compat_lint.py``) keeps direct uses out of the rest of the
tree.

``shard_map(f, mesh, in_specs, out_specs, axis_names, check_vma)``
    ``jax.shard_map``; ``axis_names`` is the set of MANUAL axes
    (``None``: every mesh axis is manual).  A strict subset leaves the
    remaining axes to GSPMD (the ``legacy_partial_auto`` train lowering).

``make_mesh(shape, axis_names)``
    ``jax.make_mesh`` with every axis Auto, which is what every call
    site requests.
"""
from __future__ import annotations

import jax
from jax import lax


def axis_size(axis) -> int:
    """Static size of a manual mesh axis (Python int inside shard_map)."""
    return lax.axis_size(axis)


def axis_index(axis):
    return lax.axis_index(axis)


def ppermute(x, axis, perm):
    return lax.ppermute(x, axis, perm)


def all_gather(x, axis):
    """``lax.all_gather`` (stacked, tiled=False)."""
    return lax.all_gather(x, axis)


def psum(x, axis):
    """``lax.psum`` over one axis or a tuple of axes."""
    return lax.psum(x, axis)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_shapes))


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with ``axis_names`` the set of MANUAL axes
    (``None`` means all mesh axes are manual)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
