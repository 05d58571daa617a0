"""The Yelp graph (arXiv 2210.17281, Sec. VI-A and Fig. 6): reviews linked
when one user wrote them, sparse, with many isolated vertices.  A copy of
the program's ``synthetic_yelp`` edge and coordinate process."""
import numpy as np

from harness.graphs import canonical, trim_to


def generate(n: int, links: int, seed: int, area: float):
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
    e = trim_to(canonical(e, n), n, links, rng)
    centers = rng.uniform(0, area, size=(8, 2))
    coords = (centers[rng.integers(0, 8, size=n)]
              + rng.normal(scale=0.6, size=(n, 2)))
    solitary = rng.uniform(size=n) < 0.1
    coords[solitary] = rng.uniform(-area * 0.3, area * 1.3,
                                   size=(int(solitary.sum()), 2))
    return e, coords.astype(np.float32)
