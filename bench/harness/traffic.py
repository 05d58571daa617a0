"""The one request generator: an open-loop schedule read from a mix's
parameters.

Targets follow a Zipf law (``zipf_s``; 0 is uniform) over a seeded rank
permutation, as the program's ``zipf_requests`` draws them.  Arrivals are
Poisson at ``rate_rps``.  Every seed of one cell gets the same work: the
multiset of targets and of inter-arrival gaps is drawn once from the mix's
``population_seed``; a run's ``--seed`` only orders them.  The gaps are
scaled so that the last request is due exactly at the window's end.
"""
from __future__ import annotations

import numpy as np


def popularity(n: int, s: float, rng) -> np.ndarray:
    """Request probability per vertex: rank^-s over a random rank order."""
    ranks = rng.permutation(n)
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    p = np.empty(n, dtype=np.float64)
    p[ranks] = w / w.sum()
    return p


def schedule(params: dict, n: int, seconds: float, seed: int):
    """(due_s, targets) of the window: ``rate_rps * seconds`` requests in
    due order, the last due at ``seconds``."""
    count = max(1, int(round(params["rate_rps"] * seconds)))
    pop = np.random.default_rng(params["population_seed"])
    p = popularity(n, params["zipf_s"], pop)
    targets = pop.choice(n, size=count, p=p).astype(np.int64)
    gaps = pop.exponential(1.0, size=count)
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    return due, rng.permutation(targets)


def warmup_targets(params: dict, n: int) -> np.ndarray:
    """The stream that fills the feature caches before the window: the
    window's law and hot set, other draws."""
    p = popularity(n, params["zipf_s"],
                   np.random.default_rng(params["population_seed"]))
    rng = np.random.default_rng([params["population_seed"], 1])
    return rng.choice(n, size=int(params["warmup_requests"]),
                      p=p).astype(np.int64)
