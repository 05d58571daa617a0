"""The benchmark's request generator."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from harness import traffic  # noqa: E402

PARAMS = {"rate_rps": 500, "zipf_s": 1.1, "population_seed": 7,
          "warmup_requests": 300}
N, SECONDS = 2000, 20


def test_same_seed_same_schedule():
    a, b = (traffic.schedule(PARAMS, N, SECONDS, 2 ** 31 + 9)
            for _ in range(2))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_seeds_reorder_the_same_work():
    (d1, t1), (d2, t2) = (traffic.schedule(PARAMS, N, SECONDS, s)
                          for s in (1, 2))
    assert not np.array_equal(t1, t2)
    np.testing.assert_array_equal(np.sort(t1), np.sort(t2))
    np.testing.assert_allclose(np.sort(np.diff(d1, prepend=0)),
                               np.sort(np.diff(d2, prepend=0)))


def test_rate_and_poisson_gaps():
    due, targets = traffic.schedule(PARAMS, N, SECONDS, 5)
    count = PARAMS["rate_rps"] * SECONDS
    assert len(due) == len(targets) == count
    assert np.all(np.diff(due) >= 0) and due[-1] == pytest.approx(SECONDS)
    gaps = np.diff(due, prepend=0.0)
    # exponential gaps: coefficient of variation 1, within sampling error
    assert abs(gaps.std() / gaps.mean() - 1) < 4 / np.sqrt(count)
    # the window's first half holds half the requests, within 4 sigma
    half = (due < SECONDS / 2).sum()
    assert abs(half - count / 2) < 4 * np.sqrt(count / 4)


def test_zipf_hot_set_is_the_warmups():
    _, targets = traffic.schedule(PARAMS, N, SECONDS, 5)
    warm = traffic.warmup_targets(PARAMS, N)
    p = traffic.popularity(N, PARAMS["zipf_s"],
                           np.random.default_rng(PARAMS["population_seed"]))
    top = int(np.argmax(p))
    for draw in (targets, warm):
        share = (draw == top).mean()
        sigma = np.sqrt(p[top] * (1 - p[top]) / len(draw))
        assert abs(share - p[top]) < 5 * sigma


def test_uniform_when_zipf_s_is_zero():
    p = traffic.popularity(N, 0.0, np.random.default_rng(0))
    np.testing.assert_allclose(p, 1.0 / N)
