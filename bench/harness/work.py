"""Useful work, counted from the graph's own sizes.

Never from the plan's padded capacities or from the BSR tiling: a PR that
retiles or replaces the aggregation kernel changes its time, not these
counts.  ``arcs`` is the number of directed arcs (twice the links); ``n``
the vertices.

Aggregation of one layer at width ``d`` (the neighbour sum): ``arcs * d``
additions; bytes are every table row read once, every output row written
once, and the two int32 indices of each arc.  A whole forward's count is
the model kind's (``bench/kinds/<kind>.py``, ``flops``).
"""
from __future__ import annotations

from harness import registry

F32 = 4
INDEX = 4


def aggregation(n: int, arcs: int, d: int) -> tuple[int, int]:
    """(flops, bytes) of one neighbour sum over the whole graph."""
    flops = arcs * d
    nbytes = 2 * n * d * F32 + 2 * arcs * INDEX
    return flops, nbytes


def model_flops(model: dict, n: int, arcs: int) -> int:
    """Operations of one whole-graph forward of the configuration's
    ``model``, counted by its kind."""
    return registry.kind(model["kind"]).flops(model, n, arcs)


def aggregation_per_forward(layer_dims, n: int, arcs: int) -> tuple[int, int]:
    """(flops, bytes) of every layer's neighbour sum in one forward."""
    flops = nbytes = 0
    for d in layer_dims[:-1]:
        f, b = aggregation(n, arcs, d)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
