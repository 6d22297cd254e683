"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Single pod: 16x16 = 256 chips over ("data", "model").
Multi-pod:  2x16x16 = 512 chips over ("pod", "data", "model"); the "pod"
axis crosses the DCN, so cross-pod traffic is only data-parallel gradient
reduction (optionally int8-compressed, repro.optim.compress).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_pod_mesh(n_pods: int):
    """Elastic-resize meshes: n_pods x 16 x 16 (n_pods=1 drops the axis)."""
    if n_pods == 1:
        return make_production_mesh(multi_pod=False)
    return jax.make_mesh((n_pods, 16, 16), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def make_cells_mesh(n_devices=None):
    """1-D ``("cells",)`` mesh for the sharded sweep engine.

    The batched simulator's scenario cells are embarrassingly parallel, so
    the mesh has a single axis: each device runs its shard of cells through
    the identical compiled scan, no collectives inside the program.  With
    ``n_devices=None`` every visible device joins; otherwise the first
    ``n_devices`` (the sweep layer clamps to the cell count and pads the
    cells axis to a device multiple).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"n_devices={n} outside [1, {len(devs)}] visible devices")
    return jax.make_mesh((n,), ("cells",), axis_types=(AxisType.Auto,),
                         devices=devs[:n])


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
