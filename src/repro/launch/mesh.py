"""Production mesh definitions.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first
jax init).
"""
from __future__ import annotations

import os

import jax

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2, pods: int = 0):
    """Small mesh over host devices for tests/examples."""
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def use_host_devices(n: int) -> None:
    """Run this process on ``n`` forced CPU host devices.  Call before
    JAX initializes a backend."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()
    jax.config.update("jax_platforms", "cpu")


def mesh_from_arg(text: str):
    """Mesh for a launcher's ``--mesh``: ``"DxM"`` or ``"PxDxM"``;
    ``""`` gives (data=every device present, model=1)."""
    dims = (tuple(int(x) for x in text.split("x")) if text
            else (len(jax.devices()), 1))
    if len(dims) not in (2, 3):
        raise ValueError(f"--mesh {text!r}: want DxM or PxDxM")
    if len(dims) == 2:
        return make_host_mesh(data=dims[0], model=dims[1])
    return make_host_mesh(pods=dims[0], data=dims[1], model=dims[2])


def dp_axes_of(mesh) -> tuple:
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))
