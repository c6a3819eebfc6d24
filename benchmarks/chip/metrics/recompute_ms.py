"""Milliseconds per step in which remat does the forward again inside
the backward: ops whose path JAX marks ``rematted_computation``
(``scopes.py``), averaged over the chips."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, lambda s: s["phases"]["recompute"])
