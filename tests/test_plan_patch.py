"""Incremental ShardPlan pipeline: patch-vs-recompile bit-identity over
randomized move/evolve sequences, capacity-growth fallbacks, empty-partition
regressions, dtype pins, move-delta threading — and a real 8-device
subprocess asserting zero jit retraces on value-only patches plus parity of
every (exchange x aggregate) path against the oracle and a dense forward."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cost import CostModel, workload_for
from repro.core.evolution import apply_delta, sample_delta
from repro.core.glad_e import glad_e
from repro.core.glad_s import glad_s
from repro.core.partition import partition_from_assign
from repro.gnn.distributed import (
    _check_int32, build_plan_bsr, compile_plan, gather_outputs, patch_plan,
    plans_equal, recompile_like, resolve_aggregate, scatter_features,
    scatter_ints, simulate_bsp_forward,
)
from repro.gnn.models import GNNConfig, directed_edges, forward, init_params
from repro.graphs.datagraph import DataGraph
from repro.graphs.edgenet import build_edge_network
from tests.conftest import random_graph


def _plan_for(g, parts, seed=0, slack=0.0):
    assign = np.random.default_rng(seed).integers(0, parts, size=g.n)
    part = partition_from_assign(g, assign, parts, {})
    return assign, compile_plan(g, part, slack=slack)


def _forward_pair(cfg, params, plan_a, plan_b, feats):
    out_a = simulate_bsp_forward(cfg, params, plan_a, feats)
    out_b = simulate_bsp_forward(cfg, params, plan_b, feats)
    np.testing.assert_array_equal(out_a, out_b)
    return out_a


# ------------------------------------------------- randomized move sequences
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000))
def test_patch_bit_identical_to_fresh_compile(seed):
    """Random relayout sequences: the patched plan is array-identical to a
    from-scratch compile at the same capacities, and its forward is
    bit-identical — growth steps (fallback rebuild) included."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(24, 64)), int(rng.integers(8, 60)))
    P = int(rng.integers(2, 6))
    slack = float(rng.choice([0.0, 0.3]))
    assign, plan = _plan_for(g, P, seed=seed, slack=slack)
    if rng.uniform() < 0.5:
        build_plan_bsr(plan, bm=4, bk=8)
    cfg = GNNConfig(str(rng.choice(["gcn", "sage"])), (8, 8, 2))
    params = init_params(jax.random.PRNGKey(seed), cfg)

    cur = assign.copy()
    for step in range(4):
        k = int(rng.integers(1, max(2, g.n // 3)))
        movers = rng.choice(g.n, size=k, replace=False)
        new = cur.copy()
        new[movers] = rng.integers(0, P, size=k)
        delta = patch_plan(plan, g, new)
        fresh = recompile_like(plan, g, new)
        assert plans_equal(plan, fresh) == []
        assert np.array_equal(np.sort(delta.moved),
                              np.flatnonzero(cur != new))
        if step % 2 == 0:
            _forward_pair(cfg, params, plan, fresh, g.features)
        cur = new


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_patch_tracks_graph_evolution(seed):
    """Evolve the graph (insert/delete links, insert AND delete vertices),
    relayout via GLAD-E, patch with the returned move delta + structure
    endpoints — patched plan bit-identical to a fresh compile every slot.

    Deleted vertices keep their id slot (the universe is append-only) but
    lose every incident arc; per the patch_plan contract their PRE-DELTA
    neighborhoods join the dirty set (the removed arcs are invisible in
    the new edge list)."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(30, 60)), int(rng.integers(20, 50)))
    P = 4
    net = build_edge_network(g, P, seed=seed)
    gnn = workload_for("gcn", 10)
    assign = glad_s(CostModel(net, g, gnn), R=2, seed=seed).assign
    plan = compile_plan(g, partition_from_assign(g, assign, P, {}), slack=0.4)
    build_plan_bsr(plan, bm=4, bk=8)

    for t in range(3):
        delta = sample_delta(g, pct_links=0.08, pct_vertices=0.05,
                             seed=seed + 17 * t)
        g_new = apply_delta(g, delta)
        net_new = build_edge_network(g_new, P, seed=seed)
        res = glad_e(CostModel(net_new, g_new, gnn), g, assign, seed=seed)
        structural = [delta.add_edges.ravel(), delta.del_edges.ravel(),
                      delta.del_vertices]
        structural += [g.neighbors(int(v)) for v in delta.del_vertices]
        structural = (np.unique(np.concatenate(structural))
                      if any(len(s) for s in structural) else None)
        pd = patch_plan(plan, g_new, res.assign, dirty_vertices=structural)
        fresh = recompile_like(plan, g_new, res.assign)
        assert plans_equal(plan, fresh) == []
        # glad_e's move delta covers every net mover + insertion.
        assert set(np.flatnonzero(
            res.assign[:g.n] != assign)) <= set(res.moved.tolist())
        assert pd.new_vertices == g_new.n - g.n
        g, assign = g_new, res.assign


def test_patch_noop_and_validation(small_siot):
    g = small_siot
    assign, plan = _plan_for(g, 4, seed=3, slack=0.2)
    v0 = plan.version
    pd = patch_plan(plan, g, assign)
    assert pd.patched and len(pd.moved) == 0 and len(pd.dirty_parts) == 0
    assert not pd.retrace_expected and plan.version == v0
    with pytest.raises(ValueError):
        patch_plan(plan, g, assign[:-1])
    bad = assign.copy()
    bad[0] = 7
    with pytest.raises(ValueError):
        patch_plan(plan, g, bad)


def test_growth_falls_back_to_doubled_rebuild(small_siot):
    """Overflowing any capacity triggers a full rebuild at doubled caps,
    still bit-identical to a pinned fresh compile, and flags the retrace."""
    g = small_siot
    assign, plan = _plan_for(g, 4, seed=1, slack=0.0)
    build_plan_bsr(plan, bm=4, bk=8)
    cap0, v0 = plan.cap, plan.version
    new = assign.copy()
    new[: g.n // 2] = 0                          # stampede into part 0
    pd = patch_plan(plan, g, new)
    assert not pd.patched and pd.grew and pd.retrace_expected
    assert plan.cap > cap0 and plan.cap % plan.pad_mult == 0
    assert plan.version == v0 + 1
    assert plans_equal(plan, recompile_like(plan, g, new)) == []
    # Relayouts within the grown headroom patch in place again.
    new2 = new.copy()
    new2[:2] = 1
    pd2 = patch_plan(plan, g, new2)
    assert pd2.patched and not pd2.grew


# --------------------------------------------------- empty-partition fallout
def test_empty_partition_plan_and_forward():
    """A server with zero members after relayout must still produce valid
    padded blocks and a correct forward (regression: zero-length groups)."""
    rng = np.random.default_rng(0)
    g = random_graph(rng, 40, 30)
    assign = np.zeros(g.n, dtype=np.int64)       # parts 1..3 empty
    plan = compile_plan(g, partition_from_assign(g, assign, 4, {}))
    assert plan.local.shape[0] == 4
    assert (plan.local[1:] == -1).all()

    blocks = scatter_features(plan, g.features)
    assert blocks.shape[:2] == (4, plan.cap)
    ints = scatter_ints(plan, np.arange(g.n), pad=-7)
    assert (ints[1:] == -7).all()
    back = gather_outputs(plan, blocks, g.n)
    np.testing.assert_array_equal(back, g.features)

    cfg = GNNConfig("gcn", (8, 8, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(forward(cfg, params, jnp.asarray(g.features),
                             jnp.asarray(directed_edges(g.edges))))
    out = simulate_bsp_forward(cfg, params, plan, g.features)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_relayout_emptying_a_partition_patches_cleanly():
    rng = np.random.default_rng(5)
    g = random_graph(rng, 36, 40)
    assign, plan = _plan_for(g, 3, seed=5, slack=1.0)
    build_plan_bsr(plan, bm=4, bk=8)
    new = assign.copy()
    new[new == 2] = 0                            # part 2 now empty
    pd = patch_plan(plan, g, new)
    assert pd.patched
    assert plans_equal(plan, recompile_like(plan, g, new)) == []
    cfg = GNNConfig("sage", (8, 8, 2))
    params = init_params(jax.random.PRNGKey(5), cfg)
    seg = simulate_bsp_forward(cfg, params, plan, g.features,
                               aggregate="segment")
    bsr = simulate_bsp_forward(cfg, params, plan, g.features,
                               aggregate="pallas")
    np.testing.assert_allclose(bsr, seg, rtol=2e-4, atol=2e-4)


def test_edgeless_graph_plan():
    g = DataGraph(n=6, edges=np.zeros((0, 2), dtype=np.int64))
    g.features = np.random.default_rng(0).normal(size=(6, 4)).astype(np.float32)
    assign = np.array([0, 0, 1, 1, 2, 2])
    plan = compile_plan(g, partition_from_assign(g, assign, 3, {}))
    assert plan.rounds == [] and plan.halo_bytes_ppermute == 0
    pd = patch_plan(plan, g, np.array([0, 1, 1, 2, 2, 0]))
    assert plans_equal(plan, recompile_like(plan, g, plan.assign)) == []
    assert pd.patched or pd.grew


# ------------------------------------------------- dtype pins / determinism
def test_plan_dtypes_and_determinism(small_siot):
    g = small_siot
    assign, plan = _plan_for(g, 4, seed=2)
    # Global slot ids (p * cap + k) overflow int32 at production P * cap:
    # pinned int64.  Per-device coordinates are bounded by table_rows and
    # guarded: pinned int32.
    assert plan.slot_of.dtype == np.int64
    assert plan.halo_slot.dtype == np.int64
    assert plan.local.dtype == np.int64
    assert plan.edges_src.dtype == np.int32
    assert plan.edges_dst.dtype == np.int32
    for r in plan.rounds:
        assert r["send_idx"].dtype == np.int32
        assert r["recv_pos"].dtype == np.int32
    # Deterministic construction: recompiling yields identical tables.
    part = partition_from_assign(g, assign, 4, {})
    again = compile_plan(g, part)
    assert plans_equal(plan, again) == []
    build_plan_bsr(plan, bm=4, bk=8)
    build_plan_bsr(again, bm=4, bk=8)
    assert plans_equal(plan, again) == []
    # Members are degree-BUCKET-ordered within each partition (BSR
    # contract): bucket floor(log2(deg)) non-increasing, vertex id
    # ascending inside each bucket — id-stable slotting across patches.
    from repro.gnn.distributed import _degree_buckets
    b = _degree_buckets(g.degrees)
    for p in range(plan.num_parts):
        vs = plan.local[p][plan.local[p] >= 0]
        db = b[vs]
        assert (np.diff(db) <= 0).all()
        for bucket in np.unique(db):
            ids = vs[db == bucket]
            assert (np.diff(ids) > 0).all()


def test_member_slots_stable_under_in_bucket_degree_drift():
    """The satellite fix for the degree-order reshuffle: a degree bump
    that stays inside its power-of-two bucket must NOT move any member's
    slot, so ``patch_plan`` only reslots parts whose bucket census truly
    changed.  (Exact-degree ordering reshuffled the whole part whenever
    one edge landed.)"""
    from repro.gnn.distributed import _part_members

    # Cycle 0-1-2-3-0: every vertex degree 2 (bucket 1).
    g0 = DataGraph(n=4, edges=np.array([[0, 1], [1, 2], [2, 3], [0, 3]]))
    # Chord 0-2: degrees of 0 and 2 become 3 — still bucket 1.
    g1 = DataGraph(n=4, edges=np.array([[0, 1], [1, 2], [2, 3], [0, 3],
                                        [0, 2]]))
    assign = np.zeros(4, dtype=np.int64)
    m0 = _part_members(g0, assign, 1)[0]
    m1 = _part_members(g1, assign, 1)[0]
    np.testing.assert_array_equal(m0, m1)
    # A bucket-crossing bump (degree 2 -> 4) DOES reorder: hub first.
    g2 = DataGraph(n=6, edges=np.array([[0, 1], [1, 2], [2, 3], [0, 3]]))
    g3 = DataGraph(n=6, edges=np.array([[0, 1], [1, 2], [2, 3], [0, 3],
                                        [2, 4], [2, 5]]))
    assign6 = np.zeros(6, dtype=np.int64)
    m2 = _part_members(g2, assign6, 1)[0]
    m3 = _part_members(g3, assign6, 1)[0]
    assert m3[0] == 2 and not np.array_equal(m2, m3)


def test_int32_guard():
    _check_int32(1 << 10, 1 << 10)               # fine
    with pytest.raises(OverflowError):
        _check_int32(1 << 31, 8)


def test_resolve_aggregate_matrix():
    gcn = GNNConfig("gcn", (4, 2))
    gat = GNNConfig("gat", (4, 2))
    assert resolve_aggregate(gcn, "segment") == "segment"
    assert resolve_aggregate(gcn, "pallas") == "pallas"
    assert resolve_aggregate(gat, "pallas") == "segment"   # softmax weights
    assert resolve_aggregate(gcn, "auto") in ("segment", "pallas")
    with pytest.raises(ValueError):
        resolve_aggregate(gcn, "nope")


# --------------------------------------------------- move-delta threading
def test_glad_s_reports_move_delta(cm_small):
    init = np.random.default_rng(0).integers(
        0, cm_small.net.m, size=cm_small.graph.n)
    res = glad_s(cm_small, R=2, init=init, seed=0, sweep="batched")
    np.testing.assert_array_equal(
        np.sort(res.moved), np.flatnonzero(res.assign != init))


def test_fault_events_carry_move_delta(small_yelp):
    from repro.runtime.fault import ElasticCoordinator
    g = small_yelp
    net = build_edge_network(g, 4, seed=0)
    gnn = workload_for("gcn", 10)
    assign = np.random.default_rng(0).integers(0, 4, size=g.n)
    part = partition_from_assign(g, assign, 4, {})
    coord = ElasticCoordinator(net, g, gnn, part)
    new_part = coord.on_failure([3], seed=0)
    ev = coord.events[-1]
    np.testing.assert_array_equal(
        np.sort(ev.moved), np.flatnonzero(new_part.assign != assign))
    np.testing.assert_array_equal(ev.moved, coord.last_moved)
    assert ev.migrated == len(ev.moved)
    # The delta drives a plan patch end-to-end.
    plan = compile_plan(g, partition_from_assign(g, assign, 4, {}), slack=0.5)
    patch_plan(plan, g, new_part.assign)
    assert plans_equal(plan, recompile_like(plan, g, new_part.assign)) == []


# ------------------------------------------------------- 8-device subprocess
_PARITY_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import synthetic_siot
    from repro.gnn import (GNNConfig, init_params, forward, directed_edges,
                           compile_plan, make_bsp_forward, scatter_features,
                           gather_outputs, simulate_bsp_forward)
    from repro.core.partition import partition_from_assign
    from repro.launch.mesh import make_mesh

    g = synthetic_siot(n=160, target_links=420)
    assign = np.random.default_rng(0).integers(0, 8, size=g.n)
    plan = compile_plan(g, partition_from_assign(g, assign, 8, {}))
    mesh = make_mesh((8,), ('data',))
    blocks = jnp.asarray(scatter_features(plan, g.features))
    sd = jnp.asarray(directed_edges(g.edges))
    combos = [(m, ex, 'segment') for m in ('gcn', 'sage', 'gat')
              for ex in ('ppermute', 'allgather')]
    combos += [(m, 'ppermute', 'pallas') for m in ('gcn', 'sage')]
    for model, ex, agg in combos:
        cfg = GNNConfig(model, (52, 16, 2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        ref = np.asarray(forward(cfg, params, jnp.asarray(g.features), sd))
        fwd = make_bsp_forward(cfg, plan, mesh, exchange=ex, aggregate=agg)
        out = gather_outputs(plan, np.asarray(fwd(params, blocks)), g.n)
        sim = simulate_bsp_forward(cfg, params, plan, g.features,
                                   aggregate=agg)
        for name, got in (('dense', ref), ('simulate', sim)):
            err = float(np.abs(got - out).max() / (np.abs(got).max() + 1e-9))
            assert err < 1e-4, (model, ex, agg, name, err)
    print('PARITY8_OK')
""")


_PATCH_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import synthetic_siot
    from repro.gnn import (GNNConfig, init_params, compile_plan, patch_plan,
                           recompile_like, plans_equal, make_bsp_forward,
                           scatter_features, gather_outputs)
    from repro.core.partition import partition_from_assign
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    g = synthetic_siot(n=240, target_links=700)
    assign = rng.integers(0, 8, size=g.n)
    plan = compile_plan(g, partition_from_assign(g, assign, 8, {}),
                        slack=0.5)
    mesh = make_mesh((8,), ('data',))
    cfg = GNNConfig('gcn', (52, 16, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    fwd = make_bsp_forward(cfg, plan, mesh, exchange='ppermute',
                           aggregate='pallas')
    blocks = jnp.asarray(scatter_features(plan, g.features))
    out0 = np.asarray(fwd(params, blocks))
    assert fwd.stats['traces'] == 1, fwd.stats

    # Value-only patches: zero retraces across a whole move sequence.
    cur = assign
    for step in range(4):
        movers = rng.choice(g.n, size=6, replace=False)
        new = cur.copy()
        new[movers] = rng.integers(0, 8, size=6)
        delta = patch_plan(plan, g, new)
        assert delta.patched and not delta.retrace_expected, vars(delta)
        fresh = recompile_like(plan, g, new)
        assert plans_equal(plan, fresh) == [], plans_equal(plan, fresh)
        out_p = np.asarray(fwd(params, blocks))
        assert fwd.stats['traces'] == 1, (step, fwd.stats)
        # Bit-identity: a fresh forward over the freshly-compiled plan.
        fwd_f = make_bsp_forward(cfg, fresh, mesh, exchange='ppermute',
                                 aggregate='pallas')
        out_f = np.asarray(fwd_f(params, blocks))
        assert np.array_equal(out_p, out_f), step
        cur = new

    # Capacity growth: exactly one recompile, result still exact.
    new = cur.copy()
    new[: g.n // 2] = 0
    delta = patch_plan(plan, g, new)
    assert (not delta.patched) and delta.retrace_expected, vars(delta)
    blocks2 = jnp.asarray(scatter_features(plan, g.features))
    out_g = np.asarray(fwd(params, blocks2))
    assert fwd.stats['traces'] == 2, fwd.stats
    fresh = recompile_like(plan, g, new)
    fwd_f = make_bsp_forward(cfg, fresh, mesh, exchange='ppermute',
                             aggregate='pallas')
    assert np.array_equal(out_g, np.asarray(fwd_f(params, blocks2)))
    assert np.array_equal(
        gather_outputs(plan, out_g, g.n)[plan.assign >= 0].shape,
        gather_outputs(fresh, out_g, g.n)[plan.assign >= 0].shape)
    print('PATCH8_OK')
""")


def _run_subprocess(script, token):
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert token in r.stdout, r.stdout + r.stderr


def test_multidevice_parity_suite_subprocess():
    _run_subprocess(_PARITY_SUBPROCESS, "PARITY8_OK")


def test_patched_plan_zero_retrace_subprocess():
    _run_subprocess(_PATCH_SUBPROCESS, "PATCH8_OK")
