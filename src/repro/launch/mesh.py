"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh_compat(shape, axes) -> Mesh:
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_elastic_mesh(
    n_devices: Optional[int] = None, model_parallelism: int = 1
) -> Mesh:
    """Best-effort mesh over whatever devices survive (elastic rebuild).

    Keeps `model_parallelism` fixed (param layout compatibility) and gives
    the rest to data parallelism — the policy a restart-after-failure uses
    when a slice comes back smaller.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n % model_parallelism != 0:
        model_parallelism = 1
    data = n // model_parallelism
    return make_mesh_compat((data, model_parallelism), ("data", "model"))


def smoke_mesh() -> Mesh:
    """1x1 mesh for CPU tests (same axis names as production)."""
    return make_mesh_compat((1, 1), ("data", "model"))
