#!/usr/bin/env python3
"""Chip smoke run: the layout -> plan -> serve path on a TPU, end to end.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four chips, the sharded path only

One chip: the paper's SIoT-shaped graph at its published size (8,001
vertices, 33,509 links, 52-d features) gets a GLAD-S layout over four edge
servers on the host and a ``ShardPlan``; ``GNNServeEngine`` answers a Zipf
request stream with the paper's 2-layer GCN (52 -> 16 -> 2, random weights
from ``--seed``); and the BSP forward runs over a one-partition plan on a
one-device mesh, where ``aggregate='auto'`` must pick the Pallas BSR kernel.

Four chips (``--four-chips``): the four chips stand in for the four edge
servers.  The sharded BSP forward runs over the 4-server layout for GCN and
SAGE (Pallas BSR) and GAT (segment sums), with both halo exchanges, and
then once more after a value-only ``patch_plan``, which must not retrace.

Every output is compared with the whole-graph forward at ``highest`` matmul
precision (``reference_forward``).  The run refuses to start off TPU.  Any
failed check exits non-zero; only a run in which every check passed prints
the last line ``{"ok": true, "device": {...}}``.  Compile seconds are the
backend compile time JAX reports (persistent-cache reads included); the
cache lives where ``repro.compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compile_cache
from repro.configs.gnn_paper import SIOT_GAT, SIOT_GCN, SIOT_SAGE
from repro.core import CostModel, workload_for
from repro.core.glad_s import glad_s
from repro.core.partition import partition_from_assign
from repro.gnn import (GNNServeEngine, compile_plan, gather_outputs,
                       init_params, make_bsp_forward, patch_plan,
                       reference_forward, scatter_features, zipf_requests)
from repro.gnn.distributed import resolve_aggregate
from repro.graphs import build_edge_network, synthetic_siot
from repro.launch.mesh import make_mesh

# TPU matmuls at default precision round their f32 operands to bf16 (unit
# roundoff 2^-9); the reference runs at ``highest``.  Through two layers of
# dot products at most 104 terms wide that leaves errors of a few parts in a
# thousand of the output's scale, so 1e-2 of the largest reference magnitude
# passes bf16 rounding and fails a wrong aggregation, which moves a row by
# the size of a whole neighbour's contribution.
REL_TOL = 1e-2
SERVERS = 4
# At build_edge_network's default mu_factor the fleet's transfer prices
# dominate on this expander graph and GLAD-S puts all 8,001 vertices on one
# server: nothing to exchange, three chips idle.  mu_factor=5 prices a
# client's distance to its server high enough that the layout spreads over
# all four servers.
MU_FACTOR = 5.0
REQUESTS = 320
BATCH = 16
MOVERS = 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rel_err(out: np.ndarray, ref: np.ndarray) -> float:
    """Largest deviation from the reference, in units of its largest
    magnitude."""
    check(out.shape == ref.shape, f"shape {out.shape} != {ref.shape}")
    check(bool(np.isfinite(out).all()), "non-finite output")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def check_close(name: str, out: np.ndarray, ref: np.ndarray) -> float:
    err = rel_err(out, ref)
    print(f"  {name}: max_rel_err={err:.3e} (tol {REL_TOL:g})", flush=True)
    check(err <= REL_TOL, f"{name}: error {err:.3e} > {REL_TOL:g}")
    return err


def layout(g, seed: int):
    """GLAD-S over a four-server fleet, solved on the host."""
    net = build_edge_network(g, SERVERS, seed=seed, mu_factor=MU_FACTOR)
    cm = CostModel(net, g, workload_for("gcn", g.features.shape[1]))
    t0 = time.perf_counter()
    res = glad_s(cm, seed=seed)
    sizes = np.bincount(res.assign, minlength=SERVERS)
    print(f"layout: GLAD-S cost {res.cost:.1f} in "
          f"{time.perf_counter() - t0:.2f}s, vertices per server "
          f"{sizes.tolist()}", flush=True)
    return net, partition_from_assign(g, res.assign, SERVERS, res.factors)


def serve_phase(cfg, params, g, net, plan, ref, seed: int) -> dict:
    targets = zipf_requests(g.n, REQUESTS, seed=seed)
    eng = GNNServeEngine(cfg, params, g, plan, batch=BATCH, net=net)
    t0 = time.perf_counter()
    out = eng.serve(targets)
    wall = time.perf_counter() - t0
    s = eng.stats
    print(f"serve: {s.requests} requests in {s.batches} batches, "
          f"{wall:.2f}s wall (compiles included), ego forward traces "
          f"{eng.fwd.stats['traces']}, rows local/cache/fetched "
          f"{s.local_rows}/{s.cache_hit_rows}/{s.fetched_rows}", flush=True)
    check(s.requests == REQUESTS, f"served {s.requests} of {REQUESTS}")
    return {"requests": s.requests,
            "max_rel_err": check_close("served outputs", out, ref[targets])}


def bsp(cfg, params, g, plan, mesh, ref, exchange: str, expect: str):
    """One sharded BSP forward over ``plan`` on ``mesh``; checks the
    aggregation path, the program, the placement and the outputs."""
    mode = resolve_aggregate(cfg, "auto")
    check(mode == expect, f"{cfg.model}: auto resolved to {mode}, "
                          f"expected {expect}")
    fwd = make_bsp_forward(cfg, plan, mesh, exchange=exchange,
                           aggregate="auto")
    sharded = NamedSharding(mesh, P("data"))
    blocks = jax.device_put(scatter_features(plan, g.features), sharded)
    out = fwd(params, blocks)
    devs = {s.device for s in out.addressable_shards}
    check(len(devs) == mesh.size,
          f"output blocks on {len(devs)} devices, mesh has {mesh.size}")
    text = fwd.lower(params, blocks).compile().as_text()
    if mode == "pallas":
        check("tpu_custom_call" in text,
              "Pallas BSR kernel missing from the compiled BSP forward")
    if mesh.size > 1:
        coll = ("collective-permute" if exchange == "ppermute"
                else "all-gather")
        check(coll in text, f"{coll} missing from the compiled program")
    err = check_close(f"bsp {cfg.model}/{mode}/{exchange} on {mesh.size} "
                      f"device(s)",
                      gather_outputs(plan, np.asarray(out), g.n), ref)
    return fwd, err


def one_chip(g, seed: int) -> dict:
    net, part = layout(g, seed)
    plan = compile_plan(g, part, slack=0.5)
    params = init_params(jax.random.PRNGKey(seed), SIOT_GCN)
    ref = reference_forward(SIOT_GCN, params, g.features, g.edges)
    served = serve_phase(SIOT_GCN, params, g, net, plan, ref, seed)

    whole = compile_plan(g, partition_from_assign(
        g, np.zeros(g.n, np.int64), 1, {}))
    _, err = bsp(SIOT_GCN, params, g, whole, make_mesh((1,), ("data",)),
                 ref, "ppermute", "pallas")
    return {"requests": served["requests"],
            "max_rel_err": max(served["max_rel_err"], err)}


def four_chips(g, seed: int) -> dict:
    check(len(jax.devices()) >= SERVERS,
          f"--four-chips needs {SERVERS} devices, found {len(jax.devices())}")
    _, part = layout(g, seed)
    check(int((np.bincount(part.assign, minlength=SERVERS) > 0).sum())
          == SERVERS, "the layout leaves a server empty")
    plan = compile_plan(g, part, slack=0.5)
    mesh = make_mesh((SERVERS,), ("data",))
    errs, patched = [], None
    for cfg, expect in ((SIOT_GCN, "pallas"), (SIOT_SAGE, "pallas"),
                        (SIOT_GAT, "segment")):
        params = init_params(jax.random.PRNGKey(seed), cfg)
        ref = reference_forward(cfg, params, g.features, g.edges)
        for exchange in ("ppermute", "allgather"):
            fwd, err = bsp(cfg, params, g, plan, mesh, ref, exchange, expect)
            errs.append(err)
            if patched is None:
                patched = (fwd, params, ref)

    # A value-only relayout: a few vertices shed to the next server.  The
    # plan is patched in place; the forward must pick it up untraced.
    fwd, params, ref = patched
    rng = np.random.default_rng(seed)
    new = plan.assign.copy()
    movers = rng.choice(g.n, size=MOVERS, replace=False)
    new[movers] = (new[movers] + 1) % SERVERS
    traces = fwd.stats["traces"]
    delta = patch_plan(plan, g, new)
    check(delta.patched and not delta.retrace_expected,
          f"the move set was not value-only: grew {delta.grew}")
    blocks = jax.device_put(scatter_features(plan, g.features),
                            NamedSharding(mesh, P("data")))
    out = gather_outputs(plan, np.asarray(fwd(params, blocks)), g.n)
    errs.append(check_close(f"bsp gcn after patching {MOVERS} movers",
                            out, ref))
    retraces = fwd.stats["traces"] - traces
    print(f"patch: {len(delta.dirty_parts)} dirty partitions, "
          f"{retraces} retraces", flush=True)
    check(retraces == 0, f"{retraces} retraces after a value-only patch")
    return {"max_rel_err": max(errs), "retraces_after_patch": retraces}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded BSP forward on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    clock = CompileClock()
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}, compile cache {cache_dir}",
          flush=True)
    t0 = time.perf_counter()
    g = synthetic_siot(seed=args.seed)
    print(f"graph: {g.n} vertices, {len(g.edges)} links, "
          f"{g.features.shape[1]}-d features", flush=True)
    try:
        res = four_chips(g, args.seed) if args.four_chips \
            else one_chip(g, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    used = SERVERS if args.four_chips else 1
    print(f"compile: {clock.seconds:.2f}s over {clock.compiles} backend "
          f"compiles, {clock.cache_hits} persistent-cache hits", flush=True)
    print(f"done: max_rel_err={res['max_rel_err']:.3e}, wall "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": used}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
