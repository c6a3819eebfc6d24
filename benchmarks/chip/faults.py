"""Faults planted underneath the timed path, to show that the check
catches them (tests, and the readings the limits are set from):

  frozen       the step returns its state unchanged (a zero update, the
               optimizer state kept);
  half_batch   the loss is the mean over the first half of each local
               batch's tokens (in row order: the first half of the rows,
               or of the positions where a chip holds one row), the rest
               left out;
  no_exchange  the gradient aggregator returns each rank's own
               gradient: no exchange between chips.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax

FAULTS = ("frozen", "half_batch", "no_exchange")


def plant(fault: str, model, opt):
    """(model, optimizer) with ``fault`` built in, where it lives there."""
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault == "frozen":
        from repro.optim import Optimizer

        def update(grads, state, params):
            return jax.tree_util.tree_map(jax.numpy.zeros_like,
                                          params), state
        opt = Optimizer(opt.init, update, opt.state_pspecs)
    elif fault == "half_batch":
        loss = model.loss

        def half(params, batch):
            shape = batch["labels"].shape
            n = math.prod(shape)
            mask = (jax.numpy.arange(n) < n // 2).reshape(shape)
            return loss(params, dict(batch, mask=mask))
        model = dataclasses.replace(model, loss=half)
    return model, opt


@contextlib.contextmanager
def _no_exchange():
    from repro.core.aggregator import GradientAggregator

    orig = GradientAggregator.__call__
    GradientAggregator.__call__ = lambda self, grads, **_: grads
    try:
        yield
    finally:
        GradientAggregator.__call__ = orig


def wrap_step(fault: str, step):
    """The step, with ``no_exchange`` in force while it is traced."""
    if fault != "no_exchange":
        return step

    def broken(*args):
        with _no_exchange():
            return step(*args)
    return broken
