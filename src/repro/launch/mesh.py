"""Mesh builders.  Functions, not module constants — importing this module
never touches jax device state (smoke tests keep 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes; every caller here wants
    the compiler to place what ``shard_map`` and the in/out specs leave
    open.  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n // model) or 1
    return make_mesh((data, model), ("data", "model"))


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
