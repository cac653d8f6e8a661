"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init.
Axes are Auto: the models place activations with sharding constraints and
let the compiler propagate the rest (JAX 0.9 otherwise makes them Explicit).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods x 256 chips as (pod=2, data=16, model=16) — the "pod"
    axis is pure data parallelism across ICI-disjoint pods (DCN-linked)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist: (data, model) with ``model``
    devices on the tensor-parallel axis."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
