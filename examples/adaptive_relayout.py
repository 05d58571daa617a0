"""Online scenario: the data graph evolves every time slot; GLAD-A decides
between incremental (GLAD-E) and global (GLAD-S) re-layout under an SLA —
and a live ShardPlan follows the layout through the incremental plan
pipeline: evolve -> relayout -> patch_plan -> resumed forward, with a full
plan recompile only when a capacity actually grows.

  PYTHONPATH=src python examples/adaptive_relayout.py [--slots 30]
"""
import argparse

import numpy as np

from repro import compile_cache
from repro.core import GladA, workload_for
from repro.core.evolution import apply_delta, evolution_trace
from repro.core.partition import partition_from_assign
from repro.gnn import (GNNConfig, compile_plan, init_params, patch_plan,
                       simulate_bsp_forward)
from repro.graphs import build_edge_network, synthetic_yelp


def main(slots: int = 30, theta: float = 10.0):
    print("== adaptive layout scheduling under graph evolution ==")
    g = synthetic_yelp(n=800, target_links=1000)
    net = build_edge_network(g, 8, seed=0)
    gnn = workload_for("gat", 100)
    sched = GladA(net, gnn, g, theta=theta, R=3, seed=0)
    print(f"initial layout cost {sched.last_cost:.1f} (SLA theta={theta})")

    # Serving side: one ShardPlan compiled with capacity headroom, then
    # PATCHED in place every slot (dirty partitions only).  A value-only
    # patch leaves every array shape unchanged, so a jitted BSP forward
    # bound to this plan would not retrace (see tests/test_plan_patch.py
    # for the retrace-count assertion on a real 8-device mesh).
    import jax
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = compile_plan(
        g, partition_from_assign(g, sched.assign, net.m, {}), slack=0.5)
    _ = simulate_bsp_forward(cfg, params, plan, g.features)
    patched = rebuilt = 0

    cur = g
    for delta in evolution_trace(g, slots, pct_links=0.02,
                                 pct_vertices=0.01, seed=1):
        new_graph = apply_delta(cur, delta)
        rec = sched.step(new_graph)
        # Structure deltas: endpoints of inserted/removed links (inserted
        # vertices are movers by construction, patch_plan derives them).
        # Deleted vertices keep their id slot but lose every incident arc
        # — those arcs are invisible in the NEW edge set, so their
        # pre-delta neighborhoods must be marked dirty explicitly.
        dirty = [delta.add_edges.ravel(), delta.del_edges.ravel(),
                 delta.del_vertices]
        dirty += [cur.neighbors(int(v)) for v in delta.del_vertices]
        dirty = np.unique(np.concatenate([d for d in dirty if len(d)])) \
            if any(len(d) for d in dirty) else None
        pd = patch_plan(plan, new_graph, sched.assign, dirty_vertices=dirty)
        patched += pd.patched
        rebuilt += not pd.patched
        out = simulate_bsp_forward(cfg, params, plan, new_graph.features)
        cur = new_graph
        bar = "#" * int(40 * min(rec.cost / sched.records[0].cost, 2) / 2)
        print(f"t={rec.t:3d} {rec.algorithm:6s} cost={rec.cost:9.1f} "
              f"drift={rec.drift_estimate:8.2f} "
              f"migrated={rec.migrated_vertices:4d} "
              f"plan={'patch' if pd.patched else 'REBUILD':7s} "
              f"dirty={len(pd.dirty_parts)}/{plan.num_parts} "
              f"emb={float(np.abs(out).mean()):.4f} |{bar}")
    n_s = sum(1 for r in sched.records[1:] if r.algorithm == "glad-s")
    print(f"GLAD-S invoked {n_s}/{slots} slots; "
          f"final cost {sched.last_cost:.1f}")
    print(f"plan lifecycle: {patched} in-place patches, {rebuilt} full "
          f"rebuilds (capacity growth), plan v{plan.version} "
          f"cap={plan.cap} halo_cap={plan.halo_cap} e_cap={plan.e_cap}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=30)
    ap.add_argument("--theta", type=float, default=10.0)
    a = ap.parse_args()
    compile_cache.enable()
    main(a.slots, a.theta)
