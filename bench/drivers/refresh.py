"""Driver of the ``refresh`` mixes: whole-graph forwards of the program's
sharded BSP engine (``make_bsp_forward``), back to back, one in flight.

The features are scattered to the devices once in set-up, as a deployment
keeps each server's rows in its memory.  Each refresh ends in
``block_until_ready`` of the sharded output.  A few refreshes, drawn from
the seed, keep their outputs for the comparison, with the last one.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import check, fleet, reference
from harness.trace import host_span

KEPT = 4          # refreshes kept for the comparison, besides the last
KEPT_AMONG = 64   # drawn among the window's first refreshes


def compared_rows(params: dict, n: int, seconds: float, seed: int):
    """The vertices whose rows a run compares: every one."""
    return slice(None)


class Driver:
    def __init__(self, cell: dict, seed: int, seconds: float, devices):
        from repro.gnn import make_bsp_forward, scatter_features
        from repro.launch.mesh import make_mesh

        cfg, prm = cell["config"], cell["params"]
        self.model = cfg["model"]
        self.ref_mode = reference.modes(cfg["model"])[0]
        parts = int(prm["partitions"])
        self.dep = fleet.build(cfg, seed, devices[0])
        _, self.plan = fleet.layout(cfg, self.dep, parts)
        mesh = make_mesh((parts,), ("data",), devices=devices[:parts])
        self.fwd = make_bsp_forward(self.dep.model, self.plan, mesh,
                                    exchange=prm["exchange"],
                                    aggregate=prm["aggregate"])
        self.params = jax.device_put(self.dep.weights,
                                     NamedSharding(mesh, P()))
        self.blocks = jax.device_put(
            scatter_features(self.plan, self.dep.feats),
            NamedSharding(mesh, P("data")))
        rng = np.random.default_rng(seed)
        self.keep_at = set(rng.choice(KEPT_AMONG, KEPT, replace=False)
                           .tolist())
        jax.block_until_ready(self.fwd(self.params, self.blocks))

    def window(self, seconds: float, spans) -> dict:
        fwd, params, blocks = self.fwd, self.params, self.blocks
        kept, count, out = [], 0, None
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with host_span(spans, "refresh"):
                out = fwd(params, blocks)
                out.block_until_ready()
            if count in self.keep_at:
                kept.append(out)
            count += 1
        wall = time.perf_counter() - t0
        self.kept = kept + [out]
        self.attempted, self.failed = count, 0
        self.window_stats = {"refreshes": count, "wall_s": wall}
        return {"refresh_ms": wall * 1e3 / max(count, 1)}

    def counters(self) -> dict:
        return dict(self.window_stats)

    def readings(self) -> dict:
        """The kept refreshes, gathered to vertex order by the program's
        ``gather_outputs``, against the reference over every vertex: the
        worst of them by each number."""
        from repro.gnn import gather_outputs

        outs = [gather_outputs(self.plan, np.asarray(o), self.dep.n)
                for o in self.kept]
        self.kept = None
        ref = reference.forward(self.model, self.dep.weights,
                                self.dep.feats_dev, self.dep.edges,
                                self.ref_mode)
        scale = check.scale_of(ref)
        got = [check.readings(o, ref, scale) for o in outs]
        return {k: max(g[k] for g in got) for k in ("max_err", "mean_err")}

    def release(self) -> None:
        self.fwd = self.blocks = None
