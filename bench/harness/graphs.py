"""The configurations' data graphs, generated by the benchmark so that the
program cannot change what it is measured on.

A configuration's ``graph.generator`` names a file
``bench/generators/<name>.py`` (``siot``, ``yelp``: the paper's two data
sets, arXiv 2210.17281, Sec. VI-A and Fig. 6), whose ``generate(n, links,
seed, area)`` gives the edges and coordinates; features and weights are
drawn on the device, see ``inputs``.  The graph is the deployment's, so it
comes from the configuration's fixed ``seed``, not from a run's ``--seed``.
"""
from __future__ import annotations

import numpy as np

from harness import registry


def canonical(edges: np.ndarray, n: int) -> np.ndarray:
    """Undirected (u < v) edge list, deduplicated, sorted, no self loops."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    _, idx = np.unique(lo * n + hi, return_index=True)
    return np.stack([lo[idx], hi[idx]], axis=1)


def trim_to(e: np.ndarray, n: int, links: int, rng) -> np.ndarray:
    """Exactly ``links`` canonical links: a draw from ``e``, or ``e`` topped
    up with random pairs."""
    if len(e) > links:
        e = e[rng.choice(len(e), size=links, replace=False)]
    while len(e) < links:
        extra = rng.integers(0, n, size=(links - len(e), 2))
        e = canonical(np.concatenate([e, extra]), n)
    return canonical(e, n)


def build(graph_cfg: dict):
    """(n, edges, coords) of a configuration's ``graph`` section."""
    gen = registry.generator(graph_cfg["generator"])
    n = int(graph_cfg["n"])
    edges, coords = gen(n, int(graph_cfg["links"]), int(graph_cfg["seed"]),
                        float(graph_cfg["area"]))
    return n, edges, coords


def directed(edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of every link: (src, dst), each (2E,)."""
    return (np.concatenate([edges[:, 0], edges[:, 1]]),
            np.concatenate([edges[:, 1], edges[:, 0]]))


def ego_sizes(n: int, edges: np.ndarray, hops: int = 2):
    """Per vertex: nodes within ``hops`` and the arcs an exact ``hops``-layer
    ego forward aggregates (every incoming arc of each distinct node closer
    than ``hops``).  Used to size the warm-up.

    The ball within ``k`` hops is the pattern of ``(I + A)^k``, kept as 0/1
    after every product so that a node reached by many walks counts once
    (``A @ A @ deg`` would count walks)."""
    import scipy.sparse as sp

    if hops < 1:
        raise ValueError(f"ego_sizes needs hops >= 1, got {hops}")
    src, dst = directed(edges)
    a = sp.csr_matrix((np.ones(len(src), np.int32), (src, dst)), shape=(n, n))
    deg = np.asarray(a.sum(axis=1)).ravel().astype(np.int64)
    ball = sp.identity(n, dtype=np.int32, format="csr")
    for _ in range(hops):
        inner = ball
        ball = ball + ball @ a
        ball.data[:] = 1
    nodes = np.diff(ball.indptr).astype(np.int64)
    arcs = inner @ deg
    return nodes, arcs
