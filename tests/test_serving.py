"""Request-driven serving: ego extraction parity vs a dense BFS oracle,
ego-forward agreement with the whole-graph reference, cache admission, and
the live-plan serving loop (including a mid-stream plan patch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partition import partition_from_assign
from repro.gnn.distributed import compile_plan, patch_plan
from repro.gnn.models import (GNNConfig, directed_edges, forward,
                              init_params, reference_forward)
from repro.gnn import serving
from repro.gnn.serving import (EgoBatch, FeatureCache, GNNServeEngine,
                               _pow2, ego_tables, extract_ego,
                               extract_ego_batch, link_traffic,
                               make_ego_forward, request_traffic,
                               serving_cost, zipf_requests)
from repro.graphs.datagraph import DataGraph, csr_multirange
from repro.graphs.edgenet import build_edge_network
from tests.conftest import random_graph

# The ego forward sums in f32 in another order than the whole-graph
# reference: its matmuls have another height, and XLA tiles (and so rounds)
# a dot by its shape.  That moves a target row by a few ulp, so agreement is
# an f32 reduction-order tolerance, on every backend, and not bit equality.
F32_REORDER = dict(rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ extraction
def _dense_bfs(g, target, hops):
    """Oracle: hop distances via dense boolean adjacency propagation."""
    adj = np.zeros((g.n, g.n), dtype=bool)
    for u, v in g.edges:
        adj[u, v] = adj[v, u] = True
    dist = np.full(g.n, -1, dtype=np.int64)
    dist[target] = 0
    frontier = np.zeros(g.n, dtype=bool)
    frontier[target] = True
    for d in range(1, hops + 1):
        frontier = adj[frontier].any(axis=0) & (dist < 0)
        dist[frontier] = d
    return dist


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_extract_ego_matches_dense_bfs(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(20, 60)), 40)
    target = int(rng.integers(0, g.n))
    hops = 2
    nodes, arcs, depth = extract_ego(g, target, hops)
    dist = _dense_bfs(g, target, hops)

    # Node set == vertices within `hops`, target first, depths exact.
    assert nodes[0] == target
    assert set(nodes.tolist()) == set(np.flatnonzero(dist >= 0).tolist())
    assert len(nodes) == len(set(nodes.tolist()))
    np.testing.assert_array_equal(depth, dist[nodes])

    # Arcs: ALL incoming arcs of every node at depth < hops, none for the
    # depth-`hops` rim, each dst's srcs in ascending order (the summation
    # order of the whole-graph forward).
    inner = nodes[depth < hops]
    adj = {}
    for u, v in g.edges:
        adj.setdefault(int(u), set()).add(int(v))
        adj.setdefault(int(v), set()).add(int(u))
    expect = {(s, int(d)) for d in inner for s in adj.get(int(d), ())}
    got = {(int(s), int(d)) for s, d in arcs}
    assert got == expect
    rim = set(nodes[depth == hops].tolist())
    assert not rim & {int(d) for _, d in arcs}
    for d in np.unique(arcs[:, 1]) if len(arcs) else []:
        srcs = arcs[arcs[:, 1] == d, 0]
        assert (np.diff(srcs) > 0).all(), f"dst {d} srcs not ascending"


def test_extract_ego_fanout_prefix_deterministic(small_siot):
    g = small_siot
    a1 = extract_ego(g, 5, 2, fanout=3)
    a2 = extract_ego(g, 5, 2, fanout=3)
    for x, y in zip(a1, a2):
        np.testing.assert_array_equal(x, y)
    nodes, arcs, _ = a1
    for d in np.unique(arcs[:, 1]):
        srcs = arcs[arcs[:, 1] == d, 0]
        assert len(srcs) <= 3
        # Ascending-id prefix of the full neighbor list.
        np.testing.assert_array_equal(srcs, g.neighbors(int(d))[:len(srcs)])


def test_extract_ego_batch_padding_invariants(small_siot):
    g = small_siot
    targets = np.array([0, 7, 31])
    ego = extract_ego_batch(g, targets, hops=2, batch=4)
    assert ego.batch == 4 and ego.targets[3] == -1
    assert ego.node_cap == 1 << (ego.node_cap.bit_length() - 1)  # pow2
    assert ego.arcs.shape[0] == 1 << (ego.arcs.shape[0].bit_length() - 1)
    # Pad arcs point at the dummy row; real arcs stay inside their request's
    # slot range; slot 0 of each live request is its target.
    assert (ego.arcs[ego.num_arcs:] == ego.dummy).all()
    for b, t in enumerate(targets):
        assert ego.nodes[b, 0] == t
        assert ego.num_nodes[b] >= 1
    real = ego.arcs[: ego.num_arcs]
    assert (real < ego.dummy).all() and (real >= 0).all()


# The per-target extraction the batched walk replaced, kept verbatim as the
# oracle: one BFS per target, then a per-request argsort/searchsorted.
def _oracle_extract_ego(graph, target, hops, fanout=None):
    indptr, indices = graph.indptr, graph.indices
    visited = np.zeros(graph.n, dtype=bool)
    visited[target] = True
    nodes = [np.array([target], dtype=np.int64)]
    depths = [np.zeros(1, dtype=np.int64)]
    srcs, dsts = [], []
    frontier = np.array([target], dtype=np.int64)
    for d in range(hops):
        if not len(frontier):
            break
        flat, rep = csr_multirange(indptr, frontier)
        nbrs = indices[flat]
        if fanout is not None and len(nbrs):
            counts = indptr[frontier + 1] - indptr[frontier]
            within = (np.arange(len(flat))
                      - np.repeat(np.cumsum(counts) - counts, counts))
            keep = within < fanout
            nbrs, rep = nbrs[keep], rep[keep]
        srcs.append(nbrs.astype(np.int64))
        dsts.append(frontier[rep])
        new = np.unique(nbrs[~visited[nbrs]])
        if len(new):
            visited[new] = True
            nodes.append(new.astype(np.int64))
            depths.append(np.full(len(new), d + 1, dtype=np.int64))
        frontier = new.astype(np.int64)
    all_nodes = np.concatenate(nodes)
    all_depth = np.concatenate(depths)
    if srcs:
        arcs = np.stack([np.concatenate(srcs), np.concatenate(dsts)], axis=1)
    else:
        arcs = np.zeros((0, 2), dtype=np.int64)
    return all_nodes, arcs, all_depth


def _oracle_extract_ego_batch(graph, targets, hops, fanout=None, batch=None):
    targets = np.asarray(targets, dtype=np.int64)
    B = int(batch) if batch is not None else len(targets)
    if len(targets) > B:
        raise ValueError(f"{len(targets)} targets > batch {B}")
    egos = [_oracle_extract_ego(graph, int(t), hops, fanout)
            for t in targets]
    node_cap = _pow2(max((len(nd) for nd, _, _ in egos), default=1))
    arc_cap = _pow2(max(sum(len(a) for _, a, _ in egos), 1))
    nodes = np.full((B, node_cap), -1, dtype=np.int64)
    num_nodes = np.zeros(B, dtype=np.int64)
    dummy = B * node_cap
    arcs = np.full((arc_cap, 2), dummy, dtype=np.int32)
    tgt = np.full(B, -1, dtype=np.int64)
    at = 0
    for b, (nd, ac, _) in enumerate(egos):
        nodes[b, : len(nd)] = nd
        num_nodes[b] = len(nd)
        tgt[b] = targets[b]
        if len(ac):
            # global -> local slot within this request (nd rows are unique).
            order = np.argsort(nd, kind="stable")
            pos = order[np.searchsorted(nd[order], ac)]
            arcs[at: at + len(ac)] = (b * node_cap + pos).astype(np.int32)
            at += len(ac)
    return EgoBatch(nodes=nodes, arcs=arcs, targets=tgt,
                    num_nodes=num_nodes, num_arcs=at, hops=hops,
                    fanout=fanout)


def _graph_with_isolated_vertices():
    """40 vertices, links only among the first 25: 15 isolated."""
    rng = np.random.default_rng(5)
    e = rng.integers(0, 25, size=(60, 2))
    return DataGraph(n=40, edges=e[e[:, 0] != e[:, 1]])


def _assert_same_batch(got, want):
    for field in ("nodes", "arcs", "targets", "num_nodes"):
        x, y = getattr(got, field), getattr(want, field)
        assert x.dtype == y.dtype, field
        np.testing.assert_array_equal(x, y, err_msg=field)
    assert got.num_arcs == want.num_arcs
    assert got.node_cap == want.node_cap
    assert got.arcs.shape[0] == want.arcs.shape[0]          # arc bucket
    assert (got.batch, got.hops, got.fanout) == \
        (want.batch, want.hops, want.fanout)


@pytest.mark.parametrize("fanout", [None, 1, 3])
@pytest.mark.parametrize("hops", [1, 2, 3])
@pytest.mark.parametrize("graph", ["random", "small_siot", "isolated"])
def test_batched_walk_matches_per_target_oracle(graph, hops, fanout,
                                                request):
    if graph == "random":
        rng = np.random.default_rng(hops * 7 + (fanout or 0))
        g = random_graph(rng, int(rng.integers(20, 60)), 40)
    elif graph == "isolated":
        g = _graph_with_isolated_vertices()
    else:
        g = request.getfixturevalue(graph)
    rng = np.random.default_rng(17)
    deg = g.degrees
    hub = int(np.argmax(deg))
    lonely = np.flatnonzero(deg == 0)
    batches = [
        (np.array([hub, 3, hub, hub, 3]), 8),      # repeated targets
        (rng.integers(0, g.n, size=8), 8),         # len == batch
        (rng.integers(0, g.n, size=3), 8),         # short batch
        (np.array([hub]), 1),
        (np.zeros(0, dtype=np.int64), 4),          # empty batch
    ]
    if len(lonely):                                # isolated targets
        batches.append((np.concatenate([lonely[:3], [hub], lonely[:1]]), 8))
        batches.append((lonely[:4], 4))
    for targets, batch in batches:
        _assert_same_batch(
            extract_ego_batch(g, targets, hops, fanout, batch=batch),
            _oracle_extract_ego_batch(g, targets, hops, fanout, batch=batch))
    for t in range(g.n):
        got = extract_ego(g, t, hops, fanout)
        want = _oracle_extract_ego(g, t, hops, fanout)
        for x, y in zip(got, want):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x, y)


# ----------------------------------------------------------------- ego forward
@pytest.mark.parametrize("jit", [True, False])
def test_ego_forward_gcn_bitmatches_oracle(jit, small_siot):
    """With full fanout the GCN ego forward reproduces the whole-graph
    reference at the target rows, jitted or eager, within f32
    reduction-order tolerance (``F32_REORDER``)."""
    g = small_siot
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    oracle = reference_forward(cfg, params, g.features, g.edges)
    targets = np.array([0, 7, 31, 149, 80])
    ego = extract_ego_batch(g, targets, hops=cfg.num_layers, batch=8)
    feats, deg, tgt = ego_tables(ego, g.features,
                                 g.degrees.astype(np.float32))
    fwd = make_ego_forward(cfg, params, jit=jit)
    out = np.asarray(fwd(jnp.asarray(feats), jnp.asarray(ego.arcs),
                         jnp.asarray(deg), jnp.asarray(tgt)))
    np.testing.assert_allclose(out[: len(targets)], oracle[targets],
                               **F32_REORDER)


def test_ego_forward_sage_eager_exact_jit_one_ulp(small_siot):
    """SAGE: the eager ego forward is bit-exact; under jit XLA splits the
    dot-of-concat ``[agg, h] @ w`` into two partial matmuls, so the jitted
    path is only allclose (~1 ulp)."""
    g = small_siot
    cfg = GNNConfig("sage", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    oracle = np.asarray(forward(cfg, params, jnp.asarray(g.features),
                                jnp.asarray(directed_edges(g.edges))))
    targets = np.array([3, 77, 140])
    ego = extract_ego_batch(g, targets, hops=cfg.num_layers, batch=4)
    feats, deg, tgt = ego_tables(ego, g.features,
                                 g.degrees.astype(np.float32))
    args = (jnp.asarray(feats), jnp.asarray(ego.arcs), jnp.asarray(deg),
            jnp.asarray(tgt))
    eager = np.asarray(make_ego_forward(cfg, params, jit=False)(*args))
    np.testing.assert_array_equal(eager[: len(targets)], oracle[targets])
    jitted = np.asarray(make_ego_forward(cfg, params)(*args))
    np.testing.assert_allclose(jitted[: len(targets)], oracle[targets],
                               **F32_REORDER)


@pytest.mark.parametrize("jit", [True, False])
def test_ego_forward_gat_within_ulp(jit, small_siot):
    """GAT: the attention logits are matvecs ``wh @ att`` whose rounding
    depends on the table height on XLA CPU, so even the eager ego path can
    flip the last bit of a softmax weight — pinned to ~1-ulp allclose."""
    g = small_siot
    cfg = GNNConfig("gat", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    oracle = np.asarray(forward(cfg, params, jnp.asarray(g.features),
                                jnp.asarray(directed_edges(g.edges))))
    targets = np.array([0, 7, 31, 149, 80])
    ego = extract_ego_batch(g, targets, hops=cfg.num_layers, batch=8)
    feats, deg, tgt = ego_tables(ego, g.features,
                                 g.degrees.astype(np.float32))
    fwd = make_ego_forward(cfg, params, jit=jit)
    out = np.asarray(fwd(jnp.asarray(feats), jnp.asarray(ego.arcs),
                         jnp.asarray(deg), jnp.asarray(tgt)))
    np.testing.assert_allclose(out[: len(targets)], oracle[targets],
                               **F32_REORDER)


def test_ego_forward_retrace_bound(small_siot):
    """Bucketed shapes: repeated batches retrace only on a NEW
    (node_cap, arc_cap) bucket pair, not per request."""
    g = small_siot
    cfg = GNNConfig("gcn", (g.features.shape[1], 8, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    fwd = make_ego_forward(cfg, params)
    rng = np.random.default_rng(0)
    shapes = set()
    for _ in range(12):
        targets = rng.choice(g.n, size=4, replace=False)
        ego = extract_ego_batch(g, targets, hops=2, batch=4)
        feats, deg, tgt = ego_tables(ego, g.features,
                                     g.degrees.astype(np.float32))
        fwd(jnp.asarray(feats), jnp.asarray(ego.arcs), jnp.asarray(deg),
            jnp.asarray(tgt))
        shapes.add((ego.node_cap, ego.arcs.shape[0]))
    assert fwd.stats["traces"] == len(shapes)
    assert fwd.stats["traces"] < 12


# ---------------------------------------------------------------- FeatureCache
def test_feature_cache_admission_discipline():
    c = FeatureCache(row_bytes=10, cache_bytes=40)     # 4 rows
    c.seed(np.array([1, 2]))                           # resident, no gate
    assert c.resident == 2
    # Under budget: admitted unconditionally.
    c.lookup(np.array([3]))
    c.admit(np.array([3]))
    c.lookup(np.array([4]))
    c.admit(np.array([4]))
    assert c.resident == 4
    # Over budget + cold (1 touch): rejected, no eviction.
    c.lookup(np.array([5]))
    c.admit(np.array([5]))
    assert c.resident == 4 and c.rejected == 1
    # Hot row (touched far more than the LRU victim): admitted, LRU evicted.
    for _ in range(5):
        c.lookup(np.array([6]))
    c.admit(np.array([6]))
    assert 6 in c._rows and c.resident == 4 and c.evictions == 1
    # Hits refresh LRU and count.
    hit = c.lookup(np.array([6, 99]))
    assert hit.tolist() == [True, False]
    assert c.hits >= 1 and c.misses >= 1


def test_feature_cache_seed_evicts_to_budget():
    c = FeatureCache(row_bytes=10, cache_bytes=25)     # 2 rows fit
    c.seed(np.arange(5))
    assert c.resident == 2 and c.evictions == 3


# --------------------------------------------------------------------- streams
def test_zipf_requests_skewed_and_deterministic():
    a = zipf_requests(100, 2000, s=1.2, seed=7)
    b = zipf_requests(100, 2000, s=1.2, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 100
    counts = np.bincount(a, minlength=100)
    assert counts.max() > 5 * counts.mean()            # skew


def test_request_traffic_mean_one():
    t = request_traffic(50, zipf_requests(50, 500, seed=1))
    assert t.shape == (50,) and abs(t.mean() - 1.0) < 1e-12
    ts = request_traffic(50, np.array([0, 0, 1]), smooth=0.5)
    assert ts.min() > 0                                 # uniform floor


def test_request_traffic_ego_propagation(small_siot):
    """With graph/hops the count of a request spreads over its whole ego:
    a single request weights every vertex of its 2-hop ball equally."""
    g = small_siot
    t = request_traffic(g.n, np.array([7]), graph=g, hops=2)
    nodes, _, _ = extract_ego(g, 7, 2)
    assert abs(t.mean() - 1.0) < 1e-12
    on = np.zeros(g.n, dtype=bool)
    on[nodes] = True
    assert (t[on] > 0).all() and (t[~on] == 0).all()
    assert np.unique(t[on]).size == 1                   # equal weight


def test_link_traffic_counts_ego_crossings(small_siot):
    """link_traffic = per canonical edge, the request mass whose ego
    contains it (each ego counts an edge once, regardless of arc
    direction), mean-1 normalized."""
    g = small_siot
    stream = np.array([7, 7, 7, 30])
    lt = link_traffic(g, stream, hops=2)
    assert lt.shape == (len(g.edges),)
    assert abs(lt.mean() - 1.0) < 1e-12

    raw = np.zeros(len(g.edges))
    keymap = {(int(a), int(b)): i for i, (a, b) in enumerate(g.edges)}
    for v, c in zip(*np.unique(stream, return_counts=True)):
        _, arcs, _ = extract_ego(g, int(v), 2)
        seen = {(min(int(a), int(b)), max(int(a), int(b)))
                for a, b in arcs}
        for k in seen:
            raw[keymap[k]] += c
    assert np.allclose(lt, raw / raw.mean())
    # Edges untouched by every ego carry zero weight.
    assert (lt[raw == 0] == 0).all() and (lt[raw > 0] > 0).all()


# --------------------------------------------------------------- serving loop
@pytest.fixture()
def served_cluster(small_siot):
    g = small_siot
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(1), cfg)
    assign = np.random.default_rng(0).integers(0, 4, size=g.n)
    plan = compile_plan(g, partition_from_assign(g, assign, 4, {}),
                        slack=0.5)
    return g, cfg, params, plan


def test_engine_serves_oracle_outputs(served_cluster):
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4)
    targets = zipf_requests(g.n, 17, seed=2)
    out = eng.serve(targets)
    oracle = reference_forward(cfg, params, g.features, g.edges)
    np.testing.assert_allclose(out, oracle[targets], **F32_REORDER)
    assert eng.stats.requests == 17
    assert eng.stats.batches == 5                       # ceil(17/4)
    assert eng.stats.local_rows + eng.stats.cache_hit_rows \
        + eng.stats.fetched_rows > 0
    assert eng.latency_percentiles()["p99"] >= \
        eng.latency_percentiles()["p50"] >= 0.0
    assert eng.stats.throughput_rps > 0


def test_engine_survives_plan_patch_mid_stream(served_cluster):
    """The fault-runtime handoff: patch_plan moves vertices mid-stream; the
    engine re-seeds caches off the new halos and keeps answering with the
    reference's outputs."""
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4)
    oracle = reference_forward(cfg, params, g.features, g.edges)
    first = np.array([0, 1, 2, 3])
    np.testing.assert_allclose(eng.serve(first), oracle[first],
                               **F32_REORDER)

    new_assign = plan.assign.copy()
    new_assign[:30] = (new_assign[:30] + 1) % 4        # relayout delta
    patch_plan(plan, g, new_assign)
    second = np.array([5, 8, 13, 21])
    np.testing.assert_allclose(eng.serve(second), oracle[second],
                               **F32_REORDER)
    assert eng.stats.plan_refreshes == 1
    cs = eng.cache_stats()
    assert cs["resident"] >= 0 and cs["hits"] + cs["misses"] >= 0


def test_engine_fetch_accounting_against_plan(served_cluster):
    """Every ego row is either local, a cache hit, or fetched — and the
    halo-seeded caches make the plan's read set hit-resident at tick 1."""
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4,
                         cache_bytes=1 << 22)
    targets = np.array([0, 40, 90, 120])
    eng.serve(targets)
    total = sum(len(extract_ego(g, int(t), cfg.num_layers)[0])
                for t in targets)
    s = eng.stats
    assert s.local_rows + s.cache_hit_rows + s.fetched_rows == total
    # Remote rows inside the home's halo are seeded -> some hits expected
    # unless every ego row happened to be local.
    if s.local_rows < total:
        assert s.cache_hit_rows + s.fetched_rows > 0


@pytest.mark.parametrize("cache_bytes", [1 << 8, 1 << 22])
def test_engine_batched_walk_serves_as_the_oracle_extraction(
        served_cluster, cache_bytes, monkeypatch):
    """The forward and the row accounting see the same egos: a Zipf stream
    served with the batched walk and with the per-target oracle patched in
    gives the same bits and the same ServeStats."""
    g, cfg, params, plan = served_cluster
    net = build_edge_network(g, 4, seed=0)
    targets = zipf_requests(g.n, 61, seed=4)

    def serve():
        eng = GNNServeEngine(cfg, params, g, plan, batch=8,
                             cache_bytes=cache_bytes, net=net)
        return eng.serve(targets), eng.stats

    out, stats = serve()
    monkeypatch.setattr(serving, "extract_ego_batch",
                        _oracle_extract_ego_batch)
    want_out, want = serve()
    np.testing.assert_array_equal(out, want_out)
    for k in ("local_rows", "cache_hit_rows", "fetched_rows", "fetch_cost",
              "rows", "row_slots", "requests", "batches"):
        assert getattr(stats, k) == getattr(want, k), k
    assert stats.fetched_rows > 0 and stats.cache_hit_rows > 0


# ------------------------------------------------------------ spans, counters
PHASES = ("serve.extract", "serve.account", "serve.tables", "serve.h2d",
          "serve.dispatch", "serve.fetch")


def test_traced_tick_records_each_phase_once_inside_the_tick(served_cluster):
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4)
    eng.submit([0, 40, 90])
    eng.spans = []
    eng.tick()
    assert sorted(n for n, _, _ in eng.spans) == sorted(PHASES
                                                        + ("serve.tick",))
    (tick,) = [sp for sp in eng.spans if sp[0] == "serve.tick"]
    phases = [sp for sp in eng.spans if sp[0] != "serve.tick"]
    assert tuple(n for n, _, _ in phases) == PHASES
    assert all(s <= e for _, s, e in eng.spans)
    assert tick[1] <= phases[0][1] and phases[-1][2] <= tick[2]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1]                  # disjoint and in order


def test_untraced_tick_records_nothing_and_serves_the_same_bits(
        served_cluster):
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4)
    targets = np.array([0, 40, 90, 120])
    assert eng.spans is None
    eng.spans = spans = []
    eng.submit(targets)
    traced = eng.tick()
    eng.spans = None
    eng.submit(targets)
    plain = eng.tick()
    assert len(spans) == 1 + len(PHASES)
    np.testing.assert_array_equal(plain, traced)


def test_row_counters_are_the_batch_counts(served_cluster):
    g, cfg, params, plan = served_cluster
    eng = GNNServeEngine(cfg, params, g, plan, batch=4)
    targets = np.array([0, 40, 90])
    ego = extract_ego_batch(g, targets, eng.hops, eng.fanout, batch=4)
    eng.submit(targets)
    eng.tick()
    assert eng.stats.rows == int(ego.num_nodes.sum())
    assert eng.stats.row_slots == ego.dummy + 1
    assert (eng.epoch_stats.rows, eng.epoch_stats.row_slots) == \
        (eng.stats.rows, eng.stats.row_slots)


def test_ego_forward_program_is_named_jit__fwd(served_cluster):
    g, cfg, params, _ = served_cluster
    ego = extract_ego_batch(g, np.array([0, 40]), cfg.num_layers, batch=4)
    feats, deg, tgt = ego_tables(ego, g.features,
                                 g.degrees.astype(np.float32))
    fwd = make_ego_forward(cfg, params)
    text = fwd.lower(jnp.asarray(feats), jnp.asarray(ego.arcs),
                     jnp.asarray(deg), jnp.asarray(tgt)).as_text()
    assert "module @jit__fwd " in text


# ---------------------------------------------------------------- serving cost
def test_serving_cost_guards_and_orders_layouts(cm_small):
    cm = cm_small
    g = cm.graph
    targets = zipf_requests(g.n, 200, seed=3)
    assign = np.random.default_rng(0).integers(0, cm.net.m, size=g.n)
    c = serving_cost(cm, assign, targets, hops=2)
    assert np.isfinite(c) and c > 0
    # A layout colocating every hot ego on its home server must not cost
    # more than the same metric with all traffic forced cross-server.
    one_home = np.zeros(g.n, dtype=np.int64)
    assert serving_cost(cm, one_home, targets, hops=2) <= c * 10  # sanity

    from repro.core.cost import CostModel
    aware = CostModel(cm.net, g, cm.gnn,
                      traffic=request_traffic(g.n, targets))
    with pytest.raises(ValueError):
        serving_cost(aware, assign, targets, hops=2)
