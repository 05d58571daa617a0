"""The SIoT graph (arXiv 2210.17281, Sec. VI-A and Fig. 6): devices whose
links follow a long-tailed degree law.  A copy of the program's
``synthetic_siot`` edge and coordinate process."""
import numpy as np

from harness.graphs import canonical, trim_to


def generate(n: int, links: int, seed: int, area: float):
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
    e = trim_to(canonical(np.stack([src, dst], axis=1), n), n, links, rng)
    coords = rng.uniform(0, area, size=(n, 2)).astype(np.float32)
    return e, coords
