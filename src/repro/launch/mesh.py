"""Production mesh construction.

Importing this module never touches jax device state; meshes are built
on demand.  Single pod: 16x16 = 256 chips ("data", "model").  Multi-pod:
2x16x16 = 512 chips ("pod", "data", "model") -- the "pod" axis is the
DCN dimension and composes with "data" for gradient reduction.
"""
from __future__ import annotations

from typing import Sequence

import jax

def _axis_type_kwargs(n_axes: int) -> dict:
    """Every mesh axis is an auto-sharded axis (jax asks explicitly)."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_elastic_mesh(devices: Sequence, model_parallel: int = 16
                      ) -> jax.sharding.Mesh:
    """Largest (data, model) mesh from the surviving device list --
    the elastic-rescale path after a node failure (runtime/elastic.py)."""
    import numpy as np
    n = len(devices)
    while model_parallel > 1 and n % model_parallel != 0:
        model_parallel //= 2
    data = n // model_parallel
    usable = data * model_parallel
    arr = np.asarray(devices[:usable]).reshape(data, model_parallel)
    return jax.sharding.Mesh(
        arr, ("data", "model"), **_axis_type_kwargs(2))
