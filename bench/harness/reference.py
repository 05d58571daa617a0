"""The plain reference: the configuration's model over the whole graph, in
``jax.numpy``, one layer at a time through its kind's file
(``bench/kinds/<kind>.py``; the paper's GCN and GraphSAGE are arXiv
2210.17281, Eqs. 1 and 3).  It imports nothing of the program and takes
only what the benchmark made: the graph's links, the features and the
weights.

The reference computes at the precision the configuration states, the same
for every mix: its ``dtype`` for every array, the neighbour sums exact in
it, and its ``matmul_precision`` for the products with the weights, on the
chip the run uses (XLA's default precision rounds a float32 dot's operands
to bfloat16 on a TPU and keeps float32 on a CPU).  A path of the program
that computes the sums at another precision, as the BSR kernel's
default-precision dot does, reads the difference as error.  The control is
the same computation with every array and every result in bfloat16.
"""
from __future__ import annotations

import functools
import json
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import registry


class Graph(NamedTuple):
    """What a kind's layer may read of the whole graph: every arc both
    ways (``src`` -> ``dst``), each vertex's in-degree, and ``n``."""
    src: jax.Array
    dst: jax.Array
    deg: jax.Array
    n: int


def modes(model: dict) -> tuple:
    """(reference, control) as (dtype, matmul precision)."""
    prec = model["matmul_precision"]
    return (model["dtype"], prec), ("bfloat16", prec)


@functools.partial(jax.jit, static_argnames=("model_json", "n", "dtype",
                                             "precision"))
def _forward(weights, feats, src, dst, *, model_json, n, dtype, precision):
    model = json.loads(model_json)
    layer = registry.kind(model["kind"]).layer
    dt = jnp.dtype(dtype)
    prec = jax.lax.Precision.HIGHEST if precision == "highest" else None
    deg = jax.ops.segment_sum(jnp.ones(src.shape, dt), dst, num_segments=n)
    g = Graph(src, dst, deg, n)
    h = feats.astype(dt)
    for k, p in enumerate(weights):
        h = layer(model, k, p, h, g, dt, prec)
    return h


def forward(model: dict, weights, feats, edges: np.ndarray,
            mode: tuple) -> np.ndarray:
    """(n, d_K) outputs of every vertex as float32 on the host."""
    n = int(feats.shape[0])
    src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int32)
    dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int32)
    out = _forward(weights, jnp.asarray(feats), jnp.asarray(src),
                   jnp.asarray(dst), model_json=json.dumps(model,
                                                           sort_keys=True),
                   n=n, dtype=mode[0], precision=mode[1])
    return np.asarray(out, dtype=np.float32)
