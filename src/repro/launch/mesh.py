"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run entry point forces 512 host
platform devices BEFORE first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(shape, axes):
    """jax.make_mesh with every axis Auto (XLA propagates shardings)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod, or (2, 16, 16) pod x data x model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally available devices (tests / examples)."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"make_host_mesh(model={model}): {n} local device(s) cannot "
            f"split into (data={n}/{model}, model={model}); pick a model "
            f"axis that divides the device count")
    return make_mesh_auto((n // model, model), ("data", "model"))


def rng_axes(mesh) -> tuple:
    """Mesh axes for the RNG block fan-out: ALL of them.

    ``engine.generate_sharded(..., axis_names=rng_axes(mesh))`` shards
    the stream axis over every device of a production mesh — the
    (host, stream) 2-D layout (or 3-D with the pod axis).  Generation is
    collective-free regardless of how the model otherwise uses the axes,
    because every column is counter-addressed from the replicated root.
    """
    return tuple(mesh.axis_names)
