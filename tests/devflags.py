"""Host-device-count bootstrap shared by the multi-device check scripts.

The check files under tests/ (multidev_*.py) run as SUBPROCESSES with N
XLA host devices while the main pytest process keeps exactly one (the
512-device override is dry-run-local; see tests/README.md).  Instead of
each script hand-rolling its own XLA_FLAGS line, the runner test sets
``REPRO_TEST_DEVICES`` and the script calls :func:`force_host_devices`
with its default before importing jax.
"""
import os
import sys

ENV_VAR = "REPRO_TEST_DEVICES"


def force_host_devices(default: int) -> int:
    """Force ``$REPRO_TEST_DEVICES`` (or ``default``) XLA host devices.

    Must run before jax is imported — XLA reads the flag once at
    backend init.  Also puts ``src/`` on sys.path so the check scripts
    work when invoked directly (``python tests/multidev_checks.py``).
    Returns the device count in effect.
    """
    if "jax" in sys.modules:
        raise RuntimeError("force_host_devices must be called before "
                           "jax is imported")
    n = int(os.environ.get(ENV_VAR, default))
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"   # host devices; never the chip
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return n
