"""Replication overlay: the move-vs-replicate greedy's exact accounting,
the ``replicate=`` solver knob (overlay never perturbs the cut trajectory),
replica tables through compile / patch_plan / set_replication (bit-identity
vs the fresh-compile oracle), the replicated multi-device forward (bit-match
vs the unreplicated plan), the serve path's replica tier + per-epoch ledger
snapshot, and the fault coordinator's degraded-mode replica fallback."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CostModel, data_partition, workload_for
from repro.core.cost import Replication
from repro.core.glad_s import glad_s
from repro.core.partition import partition_from_assign
from repro.gnn.distributed import (compile_plan, patch_plan, plans_equal,
                                   recompile_like, set_replication)
from repro.gnn.models import GNNConfig, init_params
from repro.gnn.serving import (GNNServeEngine, replicate_for_stream,
                               serving_cost, zipf_requests)
from repro.graphs.edgenet import build_edge_network
from repro.runtime import ElasticCoordinator
from tests.conftest import random_graph


def _cluster(seed=0, n=160, links=240, m=4):
    """Random graph + a fleet with real placement structure (mu_factor=2.0
    keeps compute from collapsing every vertex onto one server)."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, links)
    gnn = workload_for("gcn", g.features.shape[1])
    net = build_edge_network(g, m, seed=seed, mu_factor=2.0)
    cm = CostModel(net, g, gnn)
    assign = rng.integers(0, m, size=g.n)
    return g, gnn, net, cm, assign


def _singleton_net(cm, assign, v, p):
    """Exact net charge of replicating just v into p."""
    one = Replication(by_part={int(p): np.array([v], dtype=np.int64)},
                      gain=0.0, saved=0.0, sync=0.0, storage=0.0,
                      sync_weight=0.5, storage_cost=0.0)
    return cm.replication_cost(assign, one)["net"]


# ----------------------------------------------------------- greedy overlay
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_replicate_greedy_accounting_identity(seed):
    g, gnn, net, cm, assign = _cluster(seed)
    repl = cm.replicate_greedy(assign)
    assert repl.count > 0, "fixture should produce a non-trivial overlay"
    acc = cm.replication_cost(assign, repl)
    # The greedy accepts only positive gains, so its net is never a charge.
    assert acc["net"] <= 1e-9
    assert repl.gain == pytest.approx(-acc["net"])
    assert acc["net"] == pytest.approx(
        acc["sync"] + acc["storage"] - acc["saved"])
    assert acc["total"] == pytest.approx(cm.total(assign) + acc["net"])
    for p, ids in repl.by_part.items():
        assert (assign[ids] != p).all(), "home residents need no copy"
        assert (np.diff(ids) > 0).all(), "ids sorted unique per part"
        # Unary decisions: every accepted placement pays for itself.
        for v in ids[: min(4, len(ids))]:
            assert _singleton_net(cm, assign, int(v), int(p)) < 0


def test_replicate_greedy_budget_keeps_top_gains(seed=3):
    g, gnn, net, cm, assign = _cluster(seed)
    full = cm.replicate_greedy(assign)
    capped = cm.replicate_greedy(assign, budget=1)
    again = cm.replicate_greedy(assign, budget=1)
    for p, ids in capped.by_part.items():
        assert len(ids) <= 1
        np.testing.assert_array_equal(ids, again.by_part[p])  # deterministic
        if not len(ids) or len(full.by_part[p]) < 2:
            continue
        kept = -_singleton_net(cm, assign, int(ids[0]), p)
        for v in full.by_part[p]:
            if int(v) != int(ids[0]):
                assert kept >= -_singleton_net(cm, assign, int(v), p) - 1e-9


def test_replicate_greedy_empty_without_cut():
    g, gnn, net, cm, _ = _cluster(4)
    assign = np.zeros(g.n, dtype=np.int64)        # one server: no cut links
    repl = cm.replicate_greedy(assign)
    assert repl.count == 0
    acc = cm.replication_cost(assign, repl)
    assert acc["net"] == 0.0
    assert acc["total"] == pytest.approx(cm.total(assign))


# ------------------------------------------------------------- solver knob
def test_glad_s_replicate_never_perturbs_the_cut():
    g, gnn, net, cm, assign = _cluster(5)
    base = glad_s(cm, init=assign, R=net.m, seed=0, sweep="batched")
    repl = glad_s(cm, init=assign, R=net.m, seed=0, sweep="batched",
                  replicate=True)
    # Overlay is a post-pass: cut trajectory bit-identical with knob on/off.
    np.testing.assert_array_equal(base.assign, repl.assign)
    assert base.cost == repl.cost
    assert base.history == repl.history
    assert base.replication is None
    assert repl.replication is not None
    assert repl.replicated_cost == pytest.approx(
        repl.cost - repl.replication.gain)
    assert repl.replicated_cost <= repl.cost + 1e-9
    assert repl.repl_history is not None
    if repl.accepted:
        assert len(repl.repl_history) >= 1


def test_data_partition_replicate_attaches_overlay():
    g, gnn, net, cm, _ = _cluster(6)
    part = data_partition(g, gnn, net.m, net=net, seed=0, replicate=True)
    plain = data_partition(g, gnn, net.m, net=net, seed=0)
    np.testing.assert_array_equal(part.assign, plain.assign)
    assert plain.replication is None
    assert part.replication is not None
    # compile_plan picks the attached overlay up by default.
    plan = compile_plan(g, part, slack=0.25)
    assert plan.has_replicas == (part.replication.count > 0)


def test_coordinator_replica_fallback_and_overlay_persistence():
    g, gnn, net, cm, _ = _cluster(7, m=6)
    part = data_partition(g, gnn, 6, net=net, seed=0, replicate=True)
    assert part.replication is not None

    def run():
        coord = ElasticCoordinator(net, g, gnn, part, replicate=True)
        # Kill a server that HOMES replicated vertices, so orphans with
        # live copies exist and the fallback path actually fires.
        homed = {int(part.assign[v]) for ids in
                 part.replication.by_part.values() for v in ids}
        dead = min(homed) if homed else 0
        coord.on_failure([dead], seed=0)
        return coord, dead

    coord, dead = run()
    assert not (coord.part.assign == dead).any()
    assert coord.part.replication is not None     # overlay survives events
    assert np.isfinite(coord.events[-1].new_cost)
    coord2, _ = run()                              # fallback deterministic
    np.testing.assert_array_equal(coord.part.assign, coord2.part.assign)


# -------------------------------------------------- plan patch bit-identity
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_patch_and_set_replication_match_recompile(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(60, 140))
    g = random_graph(rng, n, int(rng.integers(40, 120)))
    m = 4
    net = build_edge_network(g, m, seed=seed % 7, mu_factor=2.0)
    cm = CostModel(net, g, workload_for("gcn", g.features.shape[1]))
    assign = rng.integers(0, m, size=n)
    plan = compile_plan(g, partition_from_assign(g, assign, m, {}),
                        slack=0.5, replication=cm.replicate_greedy(assign))
    cur = assign
    for step in range(4):
        movers = rng.choice(n, size=min(6, n), replace=False)
        new = cur.copy()
        new[movers] = rng.integers(0, m, size=len(movers))
        patch_plan(plan, g, new)
        assert plans_equal(plan, recompile_like(plan, g, new)) == []
        cur = new
        if step == 1:
            # Re-target the overlay mid-sequence (fresh greedy on the
            # moved cut), then keep patching on top of it.
            set_replication(plan, cm.replicate_greedy(cur))
            assert plans_equal(plan, recompile_like(plan, g, cur)) == []
    set_replication(plan, None)                   # clear back to replica-free
    assert not plan.has_replicas
    assert plans_equal(plan, recompile_like(plan, g, cur)) == []


def test_patch_rehomes_replicated_vertex_exactly():
    """Moving a replicated vertex ONTO its replica host (and off again)
    must re-materialize that host's replica row — the case where the
    request is stable but the materialization changes."""
    g, gnn, net, cm, assign = _cluster(8)
    repl = cm.replicate_greedy(assign)
    p, ids = next((p, ids) for p, ids in sorted(repl.by_part.items())
                  if len(ids))
    v = int(ids[0])
    plan = compile_plan(g, partition_from_assign(g, assign, net.m, {}),
                        slack=0.5, replication=repl)
    for dest in (p, int(assign[v])):              # onto the host, then back
        new = plan.assign.copy()
        new[v] = dest
        patch_plan(plan, g, new)
        assert plans_equal(plan, recompile_like(plan, g, new)) == []
        homed = v in plan.replica[p]
        assert homed == (dest != p)


# --------------------------------------------- replicated forward (8 dev)
_REPL_FWD_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import synthetic_siot
    from repro.graphs.edgenet import build_edge_network
    from repro.core import CostModel, workload_for
    from repro.core.partition import partition_from_assign
    from repro.gnn import (GNNConfig, init_params, compile_plan,
                           make_bsp_forward, scatter_features,
                           scatter_replica_halo, gather_outputs)
    from repro.launch.mesh import make_mesh

    g = synthetic_siot(n=160, target_links=420)
    assign = np.random.default_rng(0).integers(0, 8, size=g.n)
    net = build_edge_network(g, 8, seed=0, mu_factor=2.0)
    cm = CostModel(net, g, workload_for('gcn', g.features.shape[1]))
    repl = cm.replicate_greedy(assign)
    assert repl.count > 0
    part = partition_from_assign(g, assign, 8, {})
    plain = compile_plan(g, part, slack=0.25)
    rplan = compile_plan(g, part, slack=0.25, replication=repl)
    # Replica-resident rows are pruned from the layer-0 exchange.
    assert rplan.halo_bytes_ppermute0 < rplan.halo_bytes_ppermute
    mesh = make_mesh((8,), ('data',))
    blocks = jnp.asarray(scatter_features(plain, g.features))
    halo0 = jnp.asarray(scatter_replica_halo(rplan, g.features))
    params = None
    for model in ('gcn', 'sage', 'gat'):
        cfg = GNNConfig(model, (52, 16, 2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        f0 = make_bsp_forward(cfg, plain, mesh, exchange='ppermute')
        f1 = make_bsp_forward(cfg, rplan, mesh, exchange='ppermute')
        ref = gather_outputs(plain, np.asarray(f0(params, blocks)), g.n)
        out = gather_outputs(rplan, np.asarray(f1(params, blocks, halo0)),
                             g.n)
        # Replicas carry EXACT copies of what the pruned ppermute entries
        # would have delivered, so the forward is bit-identical.
        assert np.array_equal(ref, out), model
    cfg = GNNConfig('gcn', (52, 16, 2))
    f1 = make_bsp_forward(cfg, rplan, mesh, exchange='ppermute')
    try:
        f1(params, blocks)
        raise SystemExit('missing replica0 must raise')
    except ValueError:
        pass
    print('REPLFWD8_OK')
""")


def _run_subprocess(script, token):
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert token in r.stdout, r.stdout + r.stderr


def test_replicated_forward_bit_matches_unreplicated_subprocess():
    _run_subprocess(_REPL_FWD_SUBPROCESS, "REPLFWD8_OK")


# ------------------------------------------------------------- serve path
def _serving_setup(seed=0):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, 140, 220)
    m = 4
    net = build_edge_network(g, m, seed=seed, mu_factor=2.0)
    cm = CostModel(net, g, workload_for("gcn", g.features.shape[1]))
    assign = rng.integers(0, m, size=g.n)
    targets = zipf_requests(g.n, 400, s=1.1, seed=seed)
    return g, net, cm, assign, targets


def test_serving_cost_replication_identity():
    g, net, cm, assign, targets = _serving_setup(0)
    base = serving_cost(cm, assign, targets, hops=2)
    repl = replicate_for_stream(cm, assign, targets, hops=2)
    assert repl.count > 0
    got = serving_cost(cm, assign, targets, hops=2, replication=repl)
    # gain is defined against THIS stream, so the ledger closes exactly.
    assert got == pytest.approx(base - repl.gain)
    assert got <= base + 1e-9
    capped = replicate_for_stream(cm, assign, targets, hops=2, budget=2)
    assert all(len(ids) <= 2 for ids in capped.by_part.values())
    assert serving_cost(cm, assign, targets, hops=2,
                        replication=capped) <= base + 1e-9


def _drain(eng):
    while eng.tick() is not None:
        pass


def test_engine_replica_tier_served_before_cache():
    g, net, cm, assign, targets = _serving_setup(1)
    part = partition_from_assign(g, assign, net.m, {})
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 2))
    import jax
    params = init_params(jax.random.PRNGKey(0), cfg)
    repl = replicate_for_stream(cm, assign, targets, hops=2)
    plans = {
        "plain": compile_plan(g, part, slack=0.5),
        "repl": compile_plan(g, part, slack=0.5, replication=repl),
    }
    stats = {}
    for name, plan in plans.items():
        eng = GNNServeEngine(cfg, params, g, plan, hops=2, net=net,
                             cache_bytes=0)       # cache off: tier isolated
        eng.submit(targets[:160])
        _drain(eng)
        stats[name] = eng.stats
    assert stats["plain"].replica_hit_rows == 0
    assert stats["repl"].replica_hit_rows > 0
    # Same stream, same homes: remote rows only shift between tiers.
    assert stats["repl"].local_rows == stats["plain"].local_rows
    assert (stats["repl"].replica_hit_rows + stats["repl"].cache_hit_rows
            + stats["repl"].fetched_rows
            == stats["plain"].cache_hit_rows + stats["plain"].fetched_rows)
    assert stats["repl"].fetch_cost < stats["plain"].fetch_cost


def test_engine_epoch_snapshot_on_plan_patch():
    """Regression: per-epoch counters must reset when the plan re-seeds —
    post-patch throughput/p99 covers the new plan only, while the
    cumulative ledger keeps the engine's whole life."""
    g, net, cm, assign, targets = _serving_setup(2)
    part = partition_from_assign(g, assign, net.m, {})
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 2))
    import jax
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = compile_plan(g, part, slack=0.5,
                        replication=cm.replicate_greedy(assign))
    eng = GNNServeEngine(cfg, params, g, plan, hops=2, net=net)
    eng.submit(targets[:64])
    _drain(eng)
    assert eng.epoch_history == []
    first = eng.epoch_stats.requests
    assert first == 64

    rng = np.random.default_rng(9)
    movers = rng.choice(g.n, size=8, replace=False)
    new = plan.assign.copy()
    new[movers] = rng.integers(0, net.m, size=len(movers))
    patch_plan(plan, g, new)
    eng.submit(targets[64:96])
    _drain(eng)

    assert len(eng.epoch_history) == 1
    closed = eng.epoch_history[0]
    assert closed["stats"].requests == first
    assert closed["plan_version"] == eng.plan.version - 1
    assert eng.epoch_stats.requests == 32          # new window: new plan only
    assert eng.stats.requests == first + 32        # cumulative keeps both
    assert eng.stats.plan_refreshes == 1
    assert set(closed["latency"]) == {"p50", "p99"}
