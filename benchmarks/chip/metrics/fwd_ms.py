"""Milliseconds per step of the forward pass on the device: ops whose
path JAX marks ``jvp(`` and no later rule of ``scopes.py`` claims,
averaged over the chips."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, lambda s: s["phases"]["forward"])
