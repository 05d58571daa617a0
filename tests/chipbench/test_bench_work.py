"""The benchmark's useful-work counters and ego sizing against hand counts
on a 4-vertex graph, and its warm-up grid against the program's own
batching."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from harness import graphs, peaks, registry, work  # noqa: E402

# 0-1, 1-2, 2-3, 1-3: n = 4, 4 links, 8 directed arcs
EDGES = np.array([[0, 1], [1, 2], [2, 3], [1, 3]])
N, ARCS = 4, 8


def test_aggregation_counts_adds_rows_and_indices():
    flops, nbytes = work.aggregation(N, ARCS, 3)
    assert flops == 8 * 3                              # one add per arc and lane
    assert nbytes == (4 * 3 + 4 * 3) * 4 + 8 * 2 * 4   # rows in, rows out, arcs


@pytest.mark.parametrize("kind,expect", [
    # layer 3->2, then 2->1: neighbour adds, self add + degree divide,
    # matmul (SAGE: its mean's divide and a 2*d_in-wide matmul)
    ("gcn", (8 * 3 + 2 * 4 * 3 + 2 * 4 * 3 * 2)
     + (8 * 2 + 2 * 4 * 2 + 2 * 4 * 2 * 1)),
    ("sage", (8 * 3 + 4 * 3 + 2 * 4 * 6 * 2)
     + (8 * 2 + 4 * 2 + 2 * 4 * 4 * 1)),
])
def test_model_flops_by_hand(kind, expect):
    model = {"kind": kind, "layer_dims": (3, 2, 1)}
    assert work.model_flops(model, N, ARCS) == expect
    assert registry.kind(kind).flops(model, N, ARCS) == expect


def test_aggregation_per_forward_sums_hidden_widths():
    f, b = work.aggregation_per_forward((3, 2, 1), N, ARCS)
    assert f == 8 * 3 + 8 * 2
    assert b == work.aggregation(N, ARCS, 3)[1] + work.aggregation(N, ARCS, 2)[1]


def test_siot_refresh_is_about_19_6_mflop():
    f = work.model_flops({"kind": "gcn", "layer_dims": (52, 16, 2)}, 8001,
                         2 * 33509)
    assert 19.0e6 < f < 20.0e6
    assert f == 19_471_088          # the count every earlier run was read by


def test_least_time_names_its_bound():
    t, bound = peaks.least_time(1e9, 1e3, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1e9 / 197e12)
    t, bound = peaks.least_time(1.0, 819e9, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.of("cpu")


def test_ego_sizes_by_hand_and_against_the_program():
    from repro.graphs.datagraph import DataGraph
    from repro.gnn import extract_ego

    nodes, arcs = graphs.ego_sizes(N, EDGES)
    # vertex 0: ball {0,1,2,3}; arcs into 0 (1) and into 1 (3)
    assert nodes[0] == 4 and arcs[0] == 1 + 3
    g = DataGraph(n=N, edges=EDGES)
    for v in range(N):
        nd, ac, _ = extract_ego(g, v, 2)
        assert (nodes[v], arcs[v]) == (len(nd), len(ac))


def test_warmup_grid_holds_every_batch_the_program_pads():
    from repro.graphs.datagraph import DataGraph
    from repro.gnn import extract_ego_batch

    n, edges, _ = graphs.build({"generator": "siot", "n": 300,
                                "links": 1200, "seed": 3, "area": 10.0})
    g = DataGraph(n=n, edges=edges)
    nodes, arcs = graphs.ego_sizes(n, edges)
    rng = np.random.default_rng(0)
    pool = rng.choice(n, size=60, replace=False)
    bucket_grid = registry.driver("ego").bucket_grid
    grid = set(bucket_grid(nodes[pool], arcs[pool], 16))
    for _ in range(40):
        take = rng.choice(pool, size=int(rng.integers(1, 17)))
        b = extract_ego_batch(g, take, 2, None, batch=16)
        assert (b.node_cap, b.arcs.shape[0]) in grid
