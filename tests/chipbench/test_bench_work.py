"""The benchmark's useful-work counters and ego sizing against hand counts
on a 4-vertex graph, ego sizing against the program's extraction and the
parent's 2-hop formula, and its warm-up grid against the program's own
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


@pytest.mark.parametrize("kind,expect", [
    # float32: per layer rows in, rows out, the weight, the in-degree, and
    # two int32 indices per arc; layer 3->2, then 2->1 (SAGE: 2*d_in-wide w)
    ("gcn", 4 * (4 * 3 + 4 * 2 + 3 * 2 + 4) + 8 * 2 * 4
     + 4 * (4 * 2 + 4 * 1 + 2 * 1 + 4) + 8 * 2 * 4),
    ("sage", 4 * (4 * 3 + 4 * 2 + 6 * 2 + 4) + 8 * 2 * 4
     + 4 * (4 * 2 + 4 * 1 + 4 * 1 + 4) + 8 * 2 * 4),
])
def test_model_bytes_by_hand(kind, expect):
    model = {"kind": kind, "layer_dims": (3, 2, 1), "dtype": "float32"}
    assert work.model_bytes(model, N, ARCS) == expect
    assert registry.kind(kind).bytes(model, N, ARCS) == expect


@pytest.mark.parametrize("config,expect", [
    # float32, n = 8001, 2 * 33509 arcs: 52->16, 16->2
    ("siot-gcn", 4 * (8001 * 52 + 8001 * 16 + 52 * 16 + 8001) + 8 * 67018
     + 4 * (8001 * 16 + 8001 * 2 + 16 * 2 + 8001) + 8 * 67018),
    # float32, n = 3912, 2 * 4677 arcs: 100->16, 16->2, SAGE's 2*d_in-wide w
    ("yelp-sage", 4 * (3912 * 100 + 3912 * 16 + 200 * 16 + 3912) + 8 * 9354
     + 4 * (3912 * 16 + 3912 * 2 + 32 * 2 + 3912) + 8 * 9354),
])
def test_configured_forward_bytes_by_hand(config, expect):
    cfg = registry.load_json("configs", config)
    n, links = cfg["graph"]["n"], cfg["graph"]["links"]
    assert work.model_bytes(cfg["model"], n, 2 * links) == expect


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


def _parent_ego_sizes(n, edges):
    # graphs.ego_sizes as it was when it covered 2-hop egos alone
    import scipy.sparse as sp

    src, dst = graphs.directed(edges)
    a = sp.csr_matrix((np.ones(len(src), np.int32), (src, dst)), shape=(n, n))
    deg = np.asarray(a.sum(axis=1)).ravel().astype(np.int64)
    ball = (a + a @ a + sp.identity(n, dtype=np.int32, format="csr"))
    return np.diff(ball.indptr).astype(np.int64), deg + a @ deg


@pytest.mark.parametrize("config", ["siot-gcn", "yelp-sage"])
def test_two_hop_ego_sizes_are_the_parents(config):
    n, edges, _ = graphs.build(registry.load_json("configs", config)["graph"])
    for got, want in zip(graphs.ego_sizes(n, edges, 2),
                         _parent_ego_sizes(n, edges)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("hops", [1, 2, 3])
def test_ego_sizes_count_what_the_program_extracts(hops):
    from repro.graphs.datagraph import DataGraph
    from repro.gnn import extract_ego

    rng = np.random.default_rng(11)
    n = 120
    edges = graphs.canonical(rng.integers(0, n, size=(260, 2)), n)
    nodes, arcs = graphs.ego_sizes(n, edges, hops)
    g = DataGraph(n=n, edges=edges)
    for v in range(n):
        nd, ac, _ = extract_ego(g, v, hops)
        assert (nodes[v], arcs[v]) == (len(nd), len(ac)), v


def test_ego_sizes_refuse_no_hops():
    with pytest.raises(ValueError, match="hops"):
        graphs.ego_sizes(N, EDGES, 0)


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
