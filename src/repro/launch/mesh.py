"""Production mesh builders.

Single pod: (16, 16) = ("data", "model") — 256 chips (one v5e pod).
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips across 2 pods.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init; smoke tests
must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: the steps place data with
    ``with_sharding_constraint`` and leave propagation to the compiler, which
    ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """Mesh over ``devices`` (default: every device this process sees)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"), devices=devices)
