"""Device mesh management.

TPU-native replacement for the reference's device enumeration + NCCL
communicator map (platform/nccl_helper.h:56-90, gpu_info.cc): a
jax.sharding.Mesh over ICI with named axes; collectives are inserted by
GSPMD from sharding annotations rather than hand-placed allreduce ops.
Axis conventions: 'data' (DP), 'model' (TP), 'seq' (sequence/context
parallel), 'expert' (EP).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh

_current_mesh: Optional[Mesh] = None


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = ("data",),
              devices=None) -> Mesh:
    """Build a Mesh; default is 1-D data-parallel over all devices."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    arr = np.asarray(devices).reshape(tuple(shape))
    mesh = Mesh(arr, tuple(axis_names))
    set_mesh(mesh)
    return mesh


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def mesh_shape() -> Tuple[int, ...]:
    m = get_mesh()
    return tuple(m.devices.shape) if m is not None else (1,)


def num_devices() -> int:
    m = get_mesh()
    return int(np.prod(m.devices.shape)) if m is not None else 1
