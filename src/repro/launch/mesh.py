"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS for 512 host devices
before any jax import; tests and benches see 1 device).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """A mesh whose axes leave sharding propagation to XLA.  The sharding
    rules are GSPMD annotations, so the axes are ``Auto`` (``jax.make_mesh``
    defaults to ``Explicit`` sharding types)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = 256 chips/pod single-pod, or (2, 16, 16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
