"""Closed loop: request-driven GNN serving over a live layout that survives
a server failure mid-stream.

  build graph/fleet -> GLAD layout (traffic-aware) -> compile ShardPlan
  -> serve a Zipf request stream -> server dies -> ElasticCoordinator
  re-layouts -> patch_plan patches the live plan -> serving continues
  (the engine re-seeds its caches off the new halos; no rebuild).

  PYTHONPATH=src python examples/serve_gnn_requests.py [--requests 2000]
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro import compile_cache
from repro.core import CostModel, workload_for
from repro.core.glad_s import glad_s
from repro.core.partition import partition_from_assign
from repro.gnn import (GNNConfig, GNNServeEngine, compile_plan, init_params,
                       link_traffic, patch_plan, request_traffic,
                       zipf_requests)
from repro.graphs import build_edge_network, synthetic_yelp
from repro.runtime import ElasticCoordinator


def main(requests: int = 2000, servers: int = 6):
    print("== request-driven serving over a live, fault-tolerant layout ==")
    g = synthetic_yelp(n=800, target_links=1000)
    net = build_edge_network(g, servers, seed=0, mu_factor=2.0)
    gnn = workload_for("gcn", g.features.shape[1])

    # The stream is known-skewed (Zipf): hand GLAD the traffic histogram
    # (unary compute rows) and ego-crossing edge weights (pairwise C_T)
    # so hot neighborhoods dominate the placement on both axes.
    stream = zipf_requests(g.n, requests, s=1.1, seed=0)
    g_aware = dataclasses.replace(
        g, edge_weights=g.weights_or_ones() * link_traffic(g, stream, 2))
    cm = CostModel(net, g_aware, gnn,
                   traffic=request_traffic(g.n, stream, graph=g, hops=2))
    res = glad_s(cm, R=servers, seed=0, sweep="batched")
    part = partition_from_assign(g, res.assign, servers, res.factors)
    plan = compile_plan(g, part, slack=0.5)
    print(f"layout: cost {res.cost:.1f} over {servers} servers, "
          f"plan v{plan.version}")

    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = GNNServeEngine(cfg, params, g, plan, batch=16, net=net)

    half = requests // 2
    eng.serve(stream[:half])
    s = eng.stats
    print(f"first half: {s.requests} served, "
          f"{s.throughput_rps:.0f} req/s, p99 "
          f"{eng.latency_percentiles()['p99'] * 1e3:.1f} ms, rows "
          f"local/hit/fetched = {s.local_rows}/{s.cache_hit_rows}/"
          f"{s.fetched_rows}")

    # A server dies mid-stream.  The coordinator disconnects it, GLAD
    # re-layouts incrementally, and the move delta patches the LIVE plan.
    dead = int(np.bincount(part.assign, minlength=servers).argmax())
    coord = ElasticCoordinator(net, g, gnn, part)
    new_part = coord.on_failure([dead])
    ev = coord.events[-1]
    pd = patch_plan(plan, g, new_part.assign)
    print(f"server {dead} FAILED: re-layout moved {ev.migrated} vertices "
          f"in {ev.wall_time_s * 1e3:.0f} ms "
          f"(cost {ev.old_cost:.0f} -> {ev.new_cost:.0f}); plan "
          f"{'patched' if pd.patched else 'rebuilt'} to v{plan.version}, "
          f"dirty {len(pd.dirty_parts)}/{plan.num_parts} partitions")

    eng.serve(stream[half:])
    s = eng.stats
    assert not np.isin(plan.assign, [dead]).any()
    print(f"second half: {s.requests} total served, cache re-seeds "
          f"{s.plan_refreshes}, rows local/hit/fetched = "
          f"{s.local_rows}/{s.cache_hit_rows}/{s.fetched_rows}, "
          f"fetch cost {s.fetch_cost:.1f}")
    print(f"overall: {s.throughput_rps:.0f} req/s, p99 "
          f"{eng.latency_percentiles()['p99'] * 1e3:.1f} ms, "
          f"forward traces {eng.fwd.stats['traces']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--servers", type=int, default=6)
    a = ap.parse_args()
    compile_cache.enable()
    main(a.requests, a.servers)
