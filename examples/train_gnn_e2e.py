"""End-to-end driver: train a GNN node classifier over a GLAD-partitioned
graph for a few hundred steps, with checkpointing and a simulated node
failure + elastic re-layout mid-run.

  PYTHONPATH=src python examples/train_gnn_e2e.py [--steps 300]
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import data_partition, workload_for
from repro.gnn import GNNConfig, directed_edges, init_params
from repro.gnn.training import accuracy, train_step
from repro.graphs import build_edge_network, synthetic_siot
from repro.runtime import ElasticCoordinator, FailureDetector
from repro.train import CheckpointManager


def main(steps: int = 300):
    print("== distributed GNN training with GLAD layout + fault handling ==")
    g = synthetic_siot(n=1200, target_links=4000)
    gnn_w = workload_for("gcn", 52)
    net = build_edge_network(g, 6, seed=0)
    part = data_partition(g, gnn_w, num_parts=6, net=net, seed=0)
    print(f"GLAD layout: cut_links={part.cut_links} "
          f"cost={part.cost_factors['total']:.1f}")

    cfg = GNNConfig("gcn", (52, 32, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    sd = directed_edges(g.edges)
    ckdir = tempfile.mkdtemp(prefix="gnn_ck_")
    ck = CheckpointManager(ckdir, keep=2, async_write=False)

    fd = FailureDetector(6, timeout_s=5.0)
    coord = ElasticCoordinator(net, g, gnn_w, part)

    a0 = accuracy(cfg, params, g.features, sd, g.labels)
    feats, sdj, lab = (jnp.asarray(g.features), jnp.asarray(sd),
                      jnp.asarray(g.labels))
    half = steps // 2
    losses = []
    for s in range(half):
        params, loss = train_step(cfg, params, feats, sdj, lab, 0.05)
        losses.append(float(loss))
        for d in range(6):
            fd.heartbeat(d, now=float(s))
    ck.save(half, {"params": params})
    print(f"step {half}: loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"checkpointed to {ckdir}")

    # Simulate node 4 dying: detector notices, GLAD-E re-layouts survivors.
    for d in (0, 1, 2, 3, 5):
        fd.heartbeat(d, now=float(half + 6))
    dead = fd.sweep(now=float(half + 6))
    print(f"failure detected on servers {dead}")
    coord.on_failure(dead)
    ev = coord.events[-1]
    print(f"elastic re-layout: migrated={ev.migrated} vertices, "
          f"cost {ev.old_cost:.1f} -> {ev.new_cost:.1f}, "
          f"{ev.wall_time_s * 1e3:.0f} ms")

    # Restore and continue on the shrunken fleet.
    restored, _ = ck.restore(half, {"params": params})
    params = restored["params"]
    for s in range(half, steps):
        params, loss = train_step(cfg, params, feats, sdj, lab, 0.05)
        losses.append(float(loss))
    a1 = accuracy(cfg, params, g.features, sd, g.labels)
    print(f"step {steps}: loss {losses[-1]:.3f}; "
          f"accuracy {a0:.3f} -> {a1:.3f}")
    assert losses[-1] < losses[0] and a1 > a0
    print("OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    steps = ap.parse_args().steps
    compile_cache.enable()
    main(steps)
