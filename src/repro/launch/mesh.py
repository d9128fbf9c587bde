"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* jax
initializes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places arrays
    with sharding constraints that XLA propagates, which JAX refuses on
    the Explicit axes ``make_mesh`` builds by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(n_devices: int, *, pods: int = 1):
    """Smaller meshes for tests / examples (e.g. 8 fake devices)."""
    per_pod = n_devices // pods
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if per_pod % cand == 0 and cand <= per_pod:
            model = cand
            break
    data = per_pod // model
    if pods > 1:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
