"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its first
jax import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType

from repro.config import MeshConfig, MULTI_POD, SINGLE_POD


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, which reject the
    # with_sharding_constraint / gather rules the model stack is written
    # against; every mesh here is Auto
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_mesh_from_config(cfg: MeshConfig):
    return _auto_mesh(cfg.shape, cfg.axes)


def make_local_mesh(model: int = 1, data: Optional[int] = None,
                    pod: Optional[int] = None):
    """Small mesh over whatever devices exist (tests / CPU training).

    Axes are always a suffix of ("pod", "data", "model").
    """
    n = len(jax.devices())
    if data is None:
        data = n // model // (pod or 1)
    shape: Tuple[int, ...]
    if pod is not None:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    used = int(np.prod(shape))
    assert used <= n, f"mesh {shape} needs {used} devices, have {n}"
    return _auto_mesh(shape, axes)


def local_mesh_config(mesh) -> MeshConfig:
    return MeshConfig(tuple(mesh.devices.shape), tuple(mesh.axis_names))
