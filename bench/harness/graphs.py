"""The configurations' data graphs, generated here so that the program
cannot change what it is measured on.

Both generators follow the paper's description of its two data sets
(arXiv 2210.17281, Sec. VI-A and Fig. 6): SIoT, 8,001 devices and 33,509
links with a long-tailed degree law; Yelp, 3,912 reviews and 4,677 links,
sparse, with many isolated vertices.  They are copies of the program's
``synthetic_siot`` and ``synthetic_yelp`` edge and coordinate processes
(features and weights are drawn on the device, see ``inputs``).  The graph
is the deployment's, so it comes from the configuration's fixed ``seed``,
not from a run's ``--seed``.
"""
from __future__ import annotations

import numpy as np


def canonical(edges: np.ndarray, n: int) -> np.ndarray:
    """Undirected (u < v) edge list, deduplicated, sorted, no self loops."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    _, idx = np.unique(lo * n + hi, return_index=True)
    return np.stack([lo[idx], hi[idx]], axis=1)


def _trim_to(e: np.ndarray, n: int, links: int, rng) -> np.ndarray:
    if len(e) > links:
        e = e[rng.choice(len(e), size=links, replace=False)]
    while len(e) < links:
        extra = rng.integers(0, n, size=(links - len(e), 2))
        e = canonical(np.concatenate([e, extra]), n)
    return canonical(e, n)


def siot(n: int, links: int, seed: int, area: float):
    """Preferential attachment (Barabasi-Albert style), trimmed to the exact
    link count.  Returns (edges, coords)."""
    rng = np.random.default_rng(seed)
    m = max(1, int(round(links / max(n - 1, 1))))
    src, dst = [], []
    for a in range(m + 1):
        for b in range(a + 1, m + 1):
            src.append(a), dst.append(b)
    targets = list(range(m + 1)) * 2
    for v in range(m + 1, n):
        chosen = {targets[p] for p in rng.choice(len(targets), size=m,
                                                 replace=False)}
        for u in chosen:
            src.append(u), dst.append(v)
            targets.append(u)
        targets.extend([v] * len(chosen))
    e = _trim_to(canonical(np.stack([src, dst], axis=1), n), n, links, rng)
    coords = rng.uniform(0, area, size=(n, 2)).astype(np.float32)
    return e, coords


def yelp(n: int, links: int, seed: int, area: float):
    """Reviews by one user form small cliques (Pareto-sized groups), trimmed
    to the exact link count; clients sit in a downtown mixture with a
    sparse tail.  Returns (edges, coords)."""
    rng = np.random.default_rng(seed)
    edges = []
    v = 0
    while v < n:
        c = int(min(n - v, max(1, rng.pareto(2.5) + 1)))
        edges += [(a, b) for a in range(v, v + c) for b in range(a + 1, v + c)]
        v += c
    e = np.array(edges, dtype=np.int64).reshape(-1, 2)
    e = _trim_to(canonical(e, n), n, links, rng)
    centers = rng.uniform(0, area, size=(8, 2))
    coords = (centers[rng.integers(0, 8, size=n)]
              + rng.normal(scale=0.6, size=(n, 2)))
    solitary = rng.uniform(size=n) < 0.1
    coords[solitary] = rng.uniform(-area * 0.3, area * 1.3,
                                   size=(int(solitary.sum()), 2))
    return e, coords.astype(np.float32)


GENERATORS = {"siot": siot, "yelp": yelp}


def build(graph_cfg: dict):
    """(n, edges, coords) of a configuration's ``graph`` section."""
    gen = GENERATORS[graph_cfg["generator"]]
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
    ego forward aggregates (every incoming arc of each node closer than
    ``hops``).  Used to size the warm-up; only ``hops == 2`` is needed."""
    import scipy.sparse as sp

    if hops != 2:
        raise ValueError("ego_sizes covers 2-hop egos")
    src, dst = directed(edges)
    a = sp.csr_matrix((np.ones(len(src), np.int32), (src, dst)), shape=(n, n))
    deg = np.asarray(a.sum(axis=1)).ravel().astype(np.int64)
    ball = (a + a @ a + sp.identity(n, dtype=np.int32, format="csr"))
    nodes = np.diff(ball.indptr).astype(np.int64)
    arcs = deg + a @ deg
    return nodes, arcs
