"""Useful work, counted from the graph's own sizes.

Never from the plan's padded capacities or from the BSR tiling: a PR that
retiles or replaces the aggregation kernel changes its time, not these
counts.  ``arcs`` is the number of directed arcs (twice the links); ``n``
the vertices.

A whole forward's counts are the model kind's (``bench/kinds/<kind>.py``,
``flops`` and ``bytes``).  Its bytes are the least the forward moves: per
layer each input row, output row and weight once, the in-degree where the
layer reads it, and each arc's two int32 indices (``INDEX`` bytes each).
"""
from __future__ import annotations

from harness import registry

INDEX = 4       # bytes of an int32 arc endpoint


def model_flops(model: dict, n: int, arcs: int) -> int:
    """Operations of one whole-graph forward of the configuration's
    ``model``, counted by its kind."""
    return registry.kind(model["kind"]).flops(model, n, arcs)


def model_bytes(model: dict, n: int, arcs: int) -> int:
    """Bytes one whole-graph forward of the configuration's ``model`` has
    to move at the least, counted by its kind."""
    return registry.kind(model["kind"]).bytes(model, n, arcs)

