"""Jitted dispatch wrappers for the Pallas kernels.

On this CPU host the kernels run in interpret mode (Python-executed
bodies) for validation; ``on_tpu()`` flips them to compiled Mosaic
kernels. Production CPU paths (tests, small trainings) use the jnp
references — identical semantics, XLA-fused.
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .fused_adamw import adamw_update as _adamw_pallas
from .fused_reduce import fused_reduce as _reduce_pallas


@functools.partial(jax.jit, static_argnames=("use_pallas", "out_dtype"))
def fused_reduce(x, use_pallas: bool = False, out_dtype=None):
    if use_pallas:
        return _reduce_pallas(x, out_dtype=out_dtype)
    return ref.fused_reduce_ref(x, out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("use_pallas", "b1", "b2", "eps",
                                    "weight_decay"))
def adamw_update(p, g, m, v, lr, count, use_pallas: bool = False,
                 b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1):
    kw = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
              count=count)
    if use_pallas:
        return _adamw_pallas(p, g, m, v, **kw)
    return ref.adamw_update_ref(p, g, m, v, **kw)

