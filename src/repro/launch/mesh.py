"""Mesh construction.

Every mesh is built by a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules place
    params and batches with ``NamedSharding``s and let GSPMD propagate
    the rest.  ``Explicit`` axes (the default since JAX 0.7) would make
    every op that mixes a sharded operand (the embedding gather) state
    its output sharding by hand."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_launch_mesh(*, multi_pod: bool = False):
    """The mesh a launcher runs on: the production mesh when a whole
    pod is present, otherwise a (data=1, model=n) mesh over the devices
    this process sees — one chip, a four-chip host, or the CPU."""
    n = jax.device_count()
    if n >= (512 if multi_pod else 256):
        return make_production_mesh(multi_pod=multi_pod)
    return make_test_mesh(1, n)


def make_fleet_meshes(n_engines: int, data: int = 1, model: int = 1):
    """One (data, model) mesh per fleet engine, each over its own
    ``data * model`` devices, so replicas do not share a chip while the
    host has chips to spare.  Devices are dealt out in order and wrap
    around when there are fewer than ``n_engines * data * model``."""
    devs = jax.devices()
    k = data * model
    if k > len(devs):
        raise ValueError(
            f"engine mesh ({data}x{model}) needs {k} devices, found "
            f"{len(devs)}; launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={k}")
    meshes = []
    for i in range(n_engines):
        grid = np.array([devs[(i * k + j) % len(devs)] for j in range(k)])
        meshes.append(Mesh(grid.reshape(data, model), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2))
    return meshes


def make_test_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over ``data * model`` devices.

    On CPU, tests and CI get several devices by launching with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag
    must be set before jax initialises — subprocess it, never set it
    in-process after import).  ``(1, 1)`` needs no flag.
    """
    need = data * model
    have = jax.device_count()
    if have < need:
        raise ValueError(
            f"test mesh ({data}x{model}) needs {need} devices, found "
            f"{have}; launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return _auto_mesh((data, model), ("data", "model"))
