"""The plain reference: the paper's GCN and GraphSAGE layers (arXiv
2210.17281, Eqs. 1 and 3) over the whole graph, in ``jax.numpy``.  It
imports nothing of the program and takes only what the benchmark made: the
graph's links, the features and the weights.

  GCN:  h_v' = sigma(W . (sum_{u in N_v} h_u + h_v) / (|N_v| + 1))
  SAGE: h_v' = sigma(W . concat(mean_{u in N_v} h_u, h_v))

sigma is ReLU on hidden layers and the identity on the last.

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

import jax
import jax.numpy as jnp
import numpy as np


def modes(model: dict) -> tuple:
    """(reference, control) as (dtype, matmul precision)."""
    prec = model["matmul_precision"]
    return (model["dtype"], prec), ("bfloat16", prec)


@functools.partial(jax.jit, static_argnames=("kind", "n", "dtype",
                                             "precision"))
def _forward(weights, feats, src, dst, *, kind, n, dtype, precision):
    dt = jnp.dtype(dtype)
    prec = jax.lax.Precision.HIGHEST if precision == "highest" else None
    deg = jax.ops.segment_sum(jnp.ones(src.shape, dt), dst, num_segments=n)
    h = feats.astype(dt)
    last = len(weights) - 1
    for k, layer in enumerate(weights):
        w = layer["w"].astype(dt)
        agg = jax.ops.segment_sum(h[src], dst, num_segments=n)
        if kind == "gcn":
            z = (agg + h) / (deg + 1)[:, None]
        elif kind == "sage":
            z = jnp.concatenate([agg / jnp.maximum(deg, 1)[:, None], h], -1)
        else:
            raise ValueError(kind)
        out = jnp.dot(z, w, precision=prec, preferred_element_type=dt)
        h = out if k == last else jnp.maximum(out, 0)
    return h


def forward(kind: str, weights, feats, edges: np.ndarray,
            mode: tuple) -> np.ndarray:
    """(n, d_K) outputs of every vertex as float32 on the host."""
    n = int(feats.shape[0])
    src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int32)
    dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int32)
    out = _forward(weights, jnp.asarray(feats), jnp.asarray(src),
                   jnp.asarray(dst), kind=kind, n=n, dtype=mode[0],
                   precision=mode[1])
    return np.asarray(out, dtype=np.float32)
