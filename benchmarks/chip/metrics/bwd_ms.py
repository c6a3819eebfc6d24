"""Milliseconds per step of the backward pass on the device: ops whose
path JAX marks ``transpose(jvp(``, remat's recompute, the aggregator and
the optimizer left out (``scopes.py``), averaged over the chips."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, lambda s: s["phases"]["backward"])
