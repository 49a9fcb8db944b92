"""Mesh construction for every entry point.

Defined as functions so importing this module never touches jax device
state (device count is locked on first backend init). Every mesh is
built by ``make_mesh`` with ``AxisType.Auto`` on every axis: model code
pins layouts with ``with_sharding_constraint`` (``layers.constrain``),
which only accepts Auto axes, while ``jax.make_mesh`` builds Explicit
ones by default.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_local_mesh",
    "make_tp_mesh",
    "fsdp_axes",
    "MODEL_AXIS",
]

MODEL_AXIS = "model"


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` with Auto axis types. With
    ``devices`` the mesh takes exactly those devices in that order (the
    serving slices); without, ``jax.make_mesh`` picks a topology-aware
    order over all devices."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    grid = np.asarray(devices).reshape(tuple(shape))
    return Mesh(grid, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))


def make_tp_mesh(tp: int, devices=None) -> Mesh:
    """A ``(1, tp)`` slice over the first ``tp`` of ``devices`` (default
    all devices): the serving engine's tensor-parallel mesh. Unlike
    ``make_local_mesh`` it does not claim every device — data
    parallelism for serving is replica routing over disjoint slices
    (``repro.serving.router``), never a batch-sharded step, so one engine
    takes exactly ``tp`` devices."""
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < tp:
        raise ValueError(f"tp={tp} needs {tp} devices, have {len(devs)}")
    return make_mesh((1, tp), ("data", "model"), devs[:tp])


def fsdp_axes(mesh) -> tuple[str, ...]:
    """Axes parameters/optimizer state are additionally sharded over
    (ZeRO-3): the pod axis (if present) plus the data axis."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def batch_axes(mesh) -> tuple[str, ...]:
    return fsdp_axes(mesh)
