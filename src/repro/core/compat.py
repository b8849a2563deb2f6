"""Mesh helpers over the installed JAX (0.9): mesh construction from a
device list, and the ambient mesh context.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """Build a Mesh over ``devices`` (default: the first ``prod(shape)``
    devices).

    Unlike ``jax.make_mesh`` this accepts a mesh smaller than the host
    device count, so the same code runs under any
    ``--xla_force_host_platform_device_count``.  When the devices are
    all of the host's TPU chips, their order is topology-aware
    (``create_device_mesh``: ICI nearest-neighbour rings); a CPU mesh or
    a subset of the chips keeps the given order.
    """
    shape = tuple(shape)
    if devices is None:
        devices = jax.devices()[: math.prod(shape)]
    devices = list(devices)
    if len(devices) != math.prod(shape):
        raise ValueError(f"mesh shape {shape} needs {math.prod(shape)} "
                         f"devices, got {len(devices)}")
    whole_tpu_host = (devices[0].platform == "tpu"
                      and set(devices) == set(jax.local_devices()))
    if whole_tpu_host:
        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, tuple(axes))


def ambient_mesh() -> Mesh | None:
    """The mesh set by ``jax.set_mesh`` as a concrete Mesh, or None."""
    mesh = jax.sharding.get_mesh()
    return None if mesh.empty else mesh


def ambient_axis_names() -> tuple[str, ...]:
    """Axis names of the ambient abstract mesh (``jax.set_mesh`` /
    ``use_abstract_mesh``); empty outside any mesh scope.  Safe to call
    while tracing."""
    env = jax.sharding.get_abstract_mesh()
    if env is None or env.empty:
        return ()
    return tuple(env.shape.keys())
