"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — required because only dryrun.py fakes
the device count.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — used by tests/examples, not dry-runs."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))
