"""Process set-up shared by the entry points that run on the devices
present (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)."""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, so that the next run of this checkout finds what this one
# compiled.
DEFAULT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile and return its directory.  ``$JAX_COMPILATION_CACHE_DIR``,
    when set, stays in charge (JAX reads it itself); otherwise the cache
    lives in ``<checkout>/.jax_cache``."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_line() -> str:
    """``platform=... kind=... count=N`` of the devices JAX found."""
    devs = jax.devices()
    return (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")
