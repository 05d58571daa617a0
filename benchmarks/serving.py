"""Serving benchmark: request-driven inference over the live ShardPlan.

Section ``serving_cells`` — the paper's target workload (Sec. II-A: a
resident GNN service answering per-user request streams) measured
end-to-end on a yelp-shaped graph:

  * a Zipf-skewed request stream drives :class:`repro.gnn.GNNServeEngine`
    (batched k-hop ego extraction -> jitted batched forward), recording
    throughput, p50/p99 latency, ego-forward trace counts, and the
    feature-cache hit ledger against the layout's halos;
  * the SAME stream prices two GLAD layouts analytically via
    :func:`repro.gnn.serving_cost` (distributed ego execution: compute at
    each vertex's owner, one result fetch per remote row) — one layout
    computed traffic-BLIND, one traffic-aware on BOTH cost axes: the
    ego-propagated ``request_traffic`` histogram reweights the unary
    compute row, and ``link_traffic`` (egos crossing each edge) scales
    the graph's edge weights so the pairwise C_T term prices the fetch
    side too — so the cell answers the paper's placement question: does
    knowing the traffic improve the layout it serves from?  Gate:
    aware <= blind.
  * every cell replays a sample of served targets through the whole-graph
    reference ``models.reference_forward`` and counts rows outside the f32
    reduction-order tolerance (``F32_REORDER``; see
    tests/test_serving.py), so the gate is 0 mismatches.

Section ``replication_cells`` — the move-vs-replicate A/B
(:func:`run_replication_cell`): the same stream priced on the blind, the
move-only aware, and the aware-plus-replica-overlay layouts, on the
clustered yelp grid AND on the scatter/expander SIoT graph where moves
alone can't win.  Gates: ``replicated <= aware <= blind`` orderings (and
>= 1.5x vs the best move-only layout on scatter), oracle parity of the
replicated engine, and bit-identity of replica-patched plans vs fresh
compiles.

The parity/ordering quantities are integers or exact comparisons and
machine-independent; wall-clock numbers are reported but never gated.

Usage: PYTHONPATH=src python benchmarks/serving.py [--quick] [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core.cost import CostModel, workload_for
from repro.core.glad_s import glad_s
from repro.core.partition import partition_from_assign
from repro.gnn.distributed import (compile_plan, patch_plan, plans_equal,
                                   recompile_like)
from repro.gnn.models import GNNConfig, init_params, reference_forward
from repro.gnn.serving import (GNNServeEngine, link_traffic,
                               replicate_for_stream, request_traffic,
                               serving_cost, zipf_requests)
from repro.graphs.datagraph import synthetic_siot, synthetic_yelp
from repro.graphs.edgenet import build_edge_network


# Served rows agree with the whole-graph reference up to f32 summation order
# (the ego table's matmuls have another height than the whole graph's).
F32_REORDER = dict(rtol=1e-5, atol=1e-6)


def _mismatches(out: np.ndarray, ref: np.ndarray) -> int:
    """Rows of ``out`` outside the f32 reduction-order tolerance."""
    return int((~np.isclose(out, ref, **F32_REORDER)).any(axis=1).sum())


def _layouts(cm_blind, cm_aware, parts: int, seed: int):
    """Same solver, same seed, same R — the only difference is whether the
    cost model saw the traffic histogram."""
    blind = glad_s(cm_blind, R=parts, seed=seed, sweep="batched")
    aware = glad_s(cm_aware, R=parts, seed=seed, sweep="batched")
    return blind.assign, aware.assign


def run_serving_cell(n: int, parts: int, requests: int, seed: int = 0,
                     zipf_s: float = 1.1, batch: int = 8,
                     served: int = 256, parity_sample: int = 24) -> dict:
    g = synthetic_yelp(n=n, target_links=int(1.2 * n), seed=seed + 1)
    # mu_factor=2.0 gives the fleet real placement structure (the default
    # drowns C_M in compute; see the layout-engine bench methodology).
    net = build_edge_network(g, parts, seed=seed, mu_factor=2.0)
    gnn = workload_for("gcn", g.features.shape[1])
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(seed), cfg)

    hops = cfg.num_layers
    stream = zipf_requests(g.n, requests, s=zipf_s, seed=seed)
    # Ego-propagated traffic: the weight a vertex's compute row carries
    # under distributed ego execution is the number of egos touching it;
    # the weight a link carries is the number of egos crossing it (a cut
    # hot link = one result fetch per request whose ego spans it).  The
    # aware model sees both; the blind model and the serving_cost metric
    # see the plain graph.
    traffic = request_traffic(g.n, stream, graph=g, hops=hops)
    g_aware = dataclasses.replace(
        g, edge_weights=g.weights_or_ones() * link_traffic(g, stream, hops))
    cm_blind = CostModel(net, g, gnn)
    cm_aware = CostModel(net, g_aware, gnn, traffic=traffic)
    t0 = time.perf_counter()
    a_blind, a_aware = _layouts(cm_blind, cm_aware, parts, seed)
    layout_s = time.perf_counter() - t0

    cost_blind = serving_cost(cm_blind, a_blind, stream, hops)
    cost_aware = serving_cost(cm_blind, a_aware, stream, hops)

    # Serve a prefix of the stream off the traffic-aware layout.
    plan = compile_plan(
        g, partition_from_assign(g, a_aware, parts, {}), slack=0.5)
    eng = GNNServeEngine(cfg, params, g, plan, batch=batch, net=net)
    take = min(served, requests)
    eng.serve(stream[:take])
    lat = eng.latency_percentiles()
    cache = eng.cache_stats()

    # Parity replay: served outputs vs the whole-graph reference.
    oracle = reference_forward(cfg, params, g.features, g.edges)
    sample = np.unique(stream[:take])[:parity_sample]
    mismatches = _mismatches(eng.serve(sample), oracle[sample])

    s = eng.stats
    return {
        "n": n, "m": parts, "requests": requests, "zipf_s": zipf_s,
        "batch": batch, "served": take, "hops": hops, "seed": seed,
        "layout_wall_s": round(layout_s, 2),
        "serving_cost_blind": round(float(cost_blind), 3),
        "serving_cost_aware": round(float(cost_aware), 3),
        "aware_saving_pct": round(
            100.0 * (1.0 - cost_aware / max(cost_blind, 1e-12)), 2),
        "aware_leq_blind": bool(cost_aware <= cost_blind),
        "throughput_rps": round(s.throughput_rps, 1),
        "latency_p50_ms": round(lat["p50"] * 1e3, 2),
        "latency_p99_ms": round(lat["p99"] * 1e3, 2),
        "ego_rows_local": int(s.local_rows),
        "ego_rows_cache_hit": int(s.cache_hit_rows),
        "ego_rows_fetched": int(s.fetched_rows),
        "fetch_cost": round(float(s.fetch_cost), 3),
        "forward_traces": int(eng.fwd.stats["traces"]),
        "cache_resident_rows": int(cache["resident"]),
        "parity_sample": int(len(sample)),
        "parity_mismatches": mismatches,
    }


def run_replication_cell(kind: str, n: int, parts: int, requests: int,
                         seed: int = 0, zipf_s: float = 1.1, batch: int = 8,
                         served: int = 192, parity_sample: int = 16) -> dict:
    """Move-vs-replicate A/B over ONE stream window (Sec. ``replication``).

    Three layouts priced by the SAME traffic-blind :func:`serving_cost`
    on the SAME stream: traffic-blind GLAD, traffic-aware GLAD (the best
    move-only answer), and the aware layout plus the stream-greedy
    replica overlay (:func:`replicate_for_stream` — replicated rows serve
    at zero fetch, each charged its one-time sync).  ``kind='yelp'`` is
    the clustered grid where moves already help; ``kind='scatter'`` is
    the BA long-tail SIoT expander where PR 5/7 recorded that moves alone
    can't win — the fan-in regime replication exists for.  Gates:
    ``replicated <= aware`` and ``replicated <= blind`` everywhere, and
    on scatter a >= 1.5x reduction vs the BEST move-only layout.  The
    replicated plan also serves a live prefix (replica-tier ledger,
    oracle parity) and is patched through a move sweep asserting the
    replica tables stay bit-identical to fresh compiles."""
    if kind == "yelp":
        g = synthetic_yelp(n=n, target_links=int(1.2 * n), seed=seed + 1)
    elif kind == "scatter":
        g = synthetic_siot(n=n, target_links=int(3 * n), seed=seed + 1)
    else:
        raise ValueError(kind)
    net = build_edge_network(g, parts, seed=seed, mu_factor=2.0)
    gnn = workload_for("gcn", g.features.shape[1])
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 4))
    params = init_params(jax.random.PRNGKey(seed), cfg)
    hops = cfg.num_layers
    stream = zipf_requests(g.n, requests, s=zipf_s, seed=seed)

    traffic = request_traffic(g.n, stream, graph=g, hops=hops)
    g_aware = dataclasses.replace(
        g, edge_weights=g.weights_or_ones() * link_traffic(g, stream, hops))
    cm_blind = CostModel(net, g, gnn)
    cm_aware = CostModel(net, g_aware, gnn, traffic=traffic)
    t0 = time.perf_counter()
    a_blind, a_aware = _layouts(cm_blind, cm_aware, parts, seed)
    repl = replicate_for_stream(cm_blind, a_aware, stream, hops)
    layout_s = time.perf_counter() - t0

    cost_blind = serving_cost(cm_blind, a_blind, stream, hops)
    cost_aware = serving_cost(cm_blind, a_aware, stream, hops)
    cost_repl = serving_cost(cm_blind, a_aware, stream, hops,
                             replication=repl)
    best_move = min(cost_blind, cost_aware)
    ratio = best_move / max(cost_repl, 1e-12)

    # Same-window interleaved A/B: the move-only and replicated engines
    # drain the SAME request prefix tick-for-tick.
    part_aware = partition_from_assign(g, a_aware, parts, {})
    plan_move = compile_plan(g, part_aware, slack=0.5)
    plan_repl = compile_plan(g, part_aware, slack=0.5, replication=repl)
    eng_move = GNNServeEngine(cfg, params, g, plan_move, batch=batch,
                              net=net)
    eng_repl = GNNServeEngine(cfg, params, g, plan_repl, batch=batch,
                              net=net)
    take = min(served, requests)
    eng_move.submit(stream[:take])
    eng_repl.submit(stream[:take])
    while eng_move.queue or eng_repl.queue:
        eng_move.tick()
        eng_repl.tick()

    # Oracle parity on the replicated engine: replicas change where rows
    # are READ from, never the values.
    oracle = reference_forward(cfg, params, g.features, g.edges)
    sample = np.unique(stream[:take])[:parity_sample]
    mismatches = _mismatches(eng_repl.serve(sample), oracle[sample])

    # Replica patch-stability through a live move sweep.
    rng = np.random.default_rng(seed + 7)
    cur = a_aware.copy()
    patch_ok = True
    for _ in range(3):
        movers = rng.choice(g.n, size=max(g.n // 100, 4), replace=False)
        cur = cur.copy()
        cur[movers] = rng.integers(0, parts, size=len(movers))
        patch_plan(plan_repl, g, cur)
        if plans_equal(plan_repl, recompile_like(plan_repl, g, cur)):
            patch_ok = False
    sm, sr = eng_move.stats, eng_repl.stats
    return {
        "kind": kind, "n": n, "m": parts, "requests": requests,
        "zipf_s": zipf_s, "batch": batch, "served": take, "hops": hops,
        "seed": seed, "layout_wall_s": round(layout_s, 2),
        "serving_cost_blind": round(float(cost_blind), 3),
        "serving_cost_aware": round(float(cost_aware), 3),
        "serving_cost_replicated": round(float(cost_repl), 3),
        "replicas": int(repl.count),
        "replication_gain": round(float(repl.gain), 3),
        "repl_leq_aware": bool(cost_repl <= cost_aware + 1e-9),
        "repl_leq_blind": bool(cost_repl <= cost_blind + 1e-9),
        "ratio_vs_best_move": round(float(ratio), 3),
        "throughput_rps_move": round(sm.throughput_rps, 1),
        "throughput_rps_repl": round(sr.throughput_rps, 1),
        "ego_rows_local": int(sr.local_rows),
        "ego_rows_replica_hit": int(sr.replica_hit_rows),
        "ego_rows_cache_hit": int(sr.cache_hit_rows),
        "ego_rows_fetched": int(sr.fetched_rows),
        "move_rows_fetched": int(sm.fetched_rows + sm.cache_hit_rows),
        "parity_sample": int(len(sample)),
        "parity_mismatches": mismatches,
        "patch_bit_identical": bool(patch_ok),
    }


def _merge(out_path: str, cells: list, key: str = "serving_cells") -> None:
    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    doc[key] = cells
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"merged {key} into {out_path}")


def _verify(cells: list) -> list:
    bad = []
    for c in cells:
        tag = f"n={c['n']} m={c['m']}"
        if c.get("parity_mismatches", 1) != 0:
            bad.append(f"{tag}: {c['parity_mismatches']} served outputs "
                       f"diverged from the whole-graph oracle")
        if not c.get("aware_leq_blind", False):
            bad.append(f"{tag}: traffic-aware layout served WORSE than "
                       f"blind ({c['serving_cost_aware']} > "
                       f"{c['serving_cost_blind']})")
        if c.get("throughput_rps", 0) <= 0:
            bad.append(f"{tag}: zero serving throughput")
    return bad


def _verify_replication(cells: list) -> list:
    bad = []
    for c in cells:
        tag = f"{c['kind']} n={c['n']} m={c['m']}"
        if c.get("parity_mismatches", 1) != 0:
            bad.append(f"{tag}: {c['parity_mismatches']} replicated served "
                       f"outputs diverged from the whole-graph oracle")
        if not c.get("repl_leq_aware", False):
            bad.append(f"{tag}: replicated layout served WORSE than "
                       f"move-only aware ({c['serving_cost_replicated']} > "
                       f"{c['serving_cost_aware']})")
        if not c.get("repl_leq_blind", False):
            bad.append(f"{tag}: replicated layout served WORSE than blind "
                       f"({c['serving_cost_replicated']} > "
                       f"{c['serving_cost_blind']})")
        if not c.get("patch_bit_identical", False):
            bad.append(f"{tag}: patched replica plan diverged from the "
                       f"fresh compile")
        if c["kind"] == "scatter" and c.get("ratio_vs_best_move", 0) < 1.5:
            bad.append(f"{tag}: replication won only "
                       f"{c.get('ratio_vs_best_move')}x vs the best "
                       f"move-only layout (gate: >= 1.5x on scatter)")
        if (c.get("throughput_rps_move", 0) <= 0
                or c.get("throughput_rps_repl", 0) <= 0):
            bad.append(f"{tag}: zero serving throughput")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small cell only (n=800)")
    ap.add_argument("--out", default="BENCH_layout.json")
    ap.add_argument("--fail-on-mismatch", action="store_true",
                    help="exit nonzero on oracle-parity mismatches or a "
                         "traffic-aware layout that serves worse than "
                         "blind (the CI smoke gate)")
    args = ap.parse_args(argv)

    grid = [(800, 6, 4000)]
    repl_grid = [("yelp", 800, 6, 4000), ("scatter", 800, 8, 4000)]
    if not args.quick:
        grid += [(2000, 8, 10000), (3912, 8, 20000)]
        repl_grid += [("yelp", 2000, 8, 10000), ("scatter", 2000, 8, 8000)]
    cells = []
    for n, m, reqs in grid:
        cell = run_serving_cell(n, m, reqs)
        cells.append(cell)
        print(f"n={n:>5} m={m:>2} reqs={reqs:>6}: blind "
              f"{cell['serving_cost_blind']:.0f} vs aware "
              f"{cell['serving_cost_aware']:.0f} "
              f"({cell['aware_saving_pct']}% saved)  "
              f"{cell['throughput_rps']} req/s p99 "
              f"{cell['latency_p99_ms']}ms  traces "
              f"{cell['forward_traces']}  parity mismatches "
              f"{cell['parity_mismatches']}/{cell['parity_sample']}")
    _merge(args.out, cells)
    repl_cells = []
    for kind, n, m, reqs in repl_grid:
        cell = run_replication_cell(kind, n, m, reqs)
        repl_cells.append(cell)
        print(f"{kind:>7} n={n:>5} m={m:>2}: blind "
              f"{cell['serving_cost_blind']:.0f} aware "
              f"{cell['serving_cost_aware']:.0f} replicated "
              f"{cell['serving_cost_replicated']:.0f} "
              f"({cell['replicas']} replicas, "
              f"{cell['ratio_vs_best_move']}x vs best move-only)  "
              f"replica rows {cell['ego_rows_replica_hit']}  parity "
              f"{cell['parity_mismatches']}/{cell['parity_sample']}  "
              f"patch-identical {cell['patch_bit_identical']}")
    _merge(args.out, repl_cells, key="replication_cells")

    if args.fail_on_mismatch:
        bad = _verify(cells) + _verify_replication(repl_cells)
        if bad:
            print("SERVING GATE FAILURES:")
            for b in bad:
                print("  " + b)
            return 1
        print("serving gate: reference parity held, traffic-aware layout "
              "serves cheaper, replication beats move-only")
    return 0


def check_parity(ref_path: str = "BENCH_layout.json") -> int:
    """Re-run the quick cell and fail on drift vs the committed numbers.

    Gated quantities are integers / exact orderings: oracle-parity
    mismatch counts (must be 0), the aware<=blind and
    replicated<=aware<=blind orderings, the ego row ledgers
    (local+replica+hit+fetched is fixed by graph, stream and layout), the
    replica count, and replica-patch bit-identity — wall-clock never
    gates."""
    with open(ref_path) as f:
        ref = json.load(f)
    ref_cells = {(c["n"], c["m"]): c for c in ref.get("serving_cells", [])}
    if not ref_cells:
        print(f"no serving_cells committed in {ref_path}; failing")
        return 1
    got = run_serving_cell(800, 6, 4000)
    bad = _verify([got])
    r = ref_cells.get((800, 6))
    if r is None:
        bad.append("committed file lacks the (n=800, m=6) cell")
    else:
        total = (got["ego_rows_local"] + got["ego_rows_cache_hit"]
                 + got["ego_rows_fetched"])
        ref_total = (r["ego_rows_local"] + r["ego_rows_cache_hit"]
                     + r["ego_rows_fetched"])
        if total != ref_total:
            bad.append(f"ego row ledger {total} != committed {ref_total} "
                       f"(extraction or layout drift)")
    ref_repl = {(c["kind"], c["n"], c["m"]): c
                for c in ref.get("replication_cells", [])}
    if not ref_repl:
        bad.append(f"no replication_cells committed in {ref_path}")
    else:
        got_r = run_replication_cell("scatter", 800, 8, 4000)
        bad += _verify_replication([got_r])
        rr = ref_repl.get(("scatter", 800, 8))
        if rr is None:
            bad.append("committed file lacks the (scatter, n=800, m=8) "
                       "replication cell")
        else:
            for f in ("replicas", "ego_rows_replica_hit"):
                if got_r[f] != rr[f]:
                    bad.append(f"replication {f} {got_r[f]} != committed "
                               f"{rr[f]} (overlay or layout drift)")
    if bad:
        print(f"SERVING PARITY CHECK FAILED against {ref_path}")
        for b in bad:
            print("  " + b)
        return 1
    print(f"serving parity OK vs {ref_path}")
    return 0


def run(full: bool = False, smoke: bool = False) -> int:
    argv = []
    if smoke or not full:
        argv.append("--quick")
    if smoke:
        argv += ["--out", "BENCH_layout.smoke.json", "--fail-on-mismatch"]
    elif not full:
        argv += ["--out", "BENCH_layout.quick.json"]
    return main(argv)


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
