"""Device meshes: every mesh in the repo is made by `make_mesh`.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: 256 chips as (16, 16) ("data", "model"); multi-pod:
2 pods = 512 chips as (2, 16, 16) ("pod", "data", "model") — the "pod"
axis crosses DCN, so the launcher maps only low-volume collectives
(data-parallel gradient reduction or pipeline stages) onto it.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """A mesh over the first prod(shape) devices with every axis `Auto`:
    GSPMD propagates shardings from the `with_sharding_constraint`s and
    jit in/out shardings the model code sets (`repro.core.sharding`)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """(data, model) mesh over the first data*model devices: chips on a
    TPU host, or CPU devices under
    --xla_force_host_platform_device_count >= data*model."""
    return make_mesh((data, model), ("data", "model"))
