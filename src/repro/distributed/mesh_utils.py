"""Mesh construction helpers.

Never touches jax device state at import time (``make_production_mesh`` in
``repro.launch.mesh`` is the launcher-facing function; these are the shared
primitives)."""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """jax.make_mesh with explicit Auto axis types (silences the 0.9 default
    flip; our models rely on GSPMD propagation + explicit constraints)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def local_mesh(axes: Sequence[str] = ("data", "model")) -> jax.sharding.Mesh:
    """A trivial mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    shape = [1] * (len(axes) - 1) + [n]
    return make_mesh(shape, axes)


def mesh_axis_size(mesh: jax.sharding.Mesh | None, axis) -> int:
    """Product size of axis (str or tuple of str), 1 for missing axes/mesh."""
    if mesh is None or axis is None:
        return 1
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    size = 1
    for a in axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return size
