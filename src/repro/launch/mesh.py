"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is data-parallel across ICI-disjoint pods.

The dry-run environment exposes 512 host devices; smaller meshes take a
prefix of the device list so both variants run in one process.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _mesh(dev_array, axes) -> Mesh:
    return Mesh(dev_array, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devices)} exist "
            "(the dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any "
            "jax import)"
        )
    dev_array = np.array(devices[:need]).reshape(shape)
    return _mesh(dev_array, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for unit tests (requires >= prod(shape) devices)."""
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices, have {len(devices)}")
    return _mesh(np.array(devices[:need]).reshape(shape), axes)
