"""Driver of the ``ego`` mixes: an open loop of per-vertex requests into the
program's ``GNNServeEngine``.

One thread submits every request that is due and then calls ``tick()``;
when nothing is queued it sleeps until the next due time.  A request's
latency runs from its due time on the schedule to the moment ``tick`` has
its output row on the host.  The loop stops at the window's close, after
the tick in progress.  Offered above the engine's capacity, the queue grows
all through the window and what counts is the requests completed in it
(``ego_served_rps``); every request answered is compared with the
reference, and the backlog left at the close is neither served nor counted.
"""
from __future__ import annotations

import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, fleet, graphs, reference, traffic
from harness.trace import host_span


STALL_FACTOR = 10   # a tick this many times the window's median is a stall


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def bucket_grid(nodes: np.ndarray, arcs: np.ndarray, batch: int) -> list:
    """Every (node_cap, arc_cap) a batch of up to ``batch`` of these egos can
    pad to: node_cap is the largest ego's power of two; arc_cap lies between
    the power of two of the fewest arcs an ego of that class has and that of
    the most arcs a full batch of egos no larger can carry."""
    cls = np.array([_pow2(x) for x in nodes])
    out = []
    for c in sorted(set(cls.tolist())):
        at_c, upto_c = arcs[cls == c], arcs[cls <= c]
        lo = _pow2(max(int(at_c.min()), 1))
        hi = _pow2(max(int(at_c.max()) + (batch - 1) * int(upto_c.max()), 1))
        a = lo
        while a <= hi:
            out.append((c, a))
            a *= 2
    return out


def compared_rows(params: dict, n: int, seconds: float, seed: int):
    """The vertices whose rows a run compares: the window's targets."""
    return traffic.schedule(params, n, seconds, seed)[1]


class Driver:
    def __init__(self, cell: dict, seed: int, seconds: float, devices):
        from repro.gnn import GNNServeEngine

        cfg, prm = cell["config"], cell["params"]
        self.prm, self.model = prm, cfg["model"]
        self.ref_mode = reference.modes(cfg["model"])[0]
        self.dep = fleet.build(cfg, seed, devices[0])
        net, plan = fleet.layout(cfg, self.dep, int(cfg["fleet"]["servers"]))
        self.engine = GNNServeEngine(
            self.dep.model, self.dep.weights, self.dep.graph, plan,
            hops=prm["hops"], fanout=prm["fanout"], batch=prm["batch"],
            cache_bytes=prm["cache_bytes"], net=net)
        n = self.dep.n
        self.due, self.targets = traffic.schedule(prm, n, seconds, seed)
        warm = traffic.warmup_targets(prm, n)
        nodes, arcs = graphs.ego_sizes(n, self.dep.edges, prm["hops"])
        used = np.unique(np.concatenate([self.targets, warm]))
        self.shapes = bucket_grid(nodes[used], arcs[used], prm["batch"])
        self._warm_shapes()
        self.engine.serve(warm)          # fills the feature caches
        self.out = np.zeros((len(self.targets), cfg["model"]["layer_dims"][-1]),
                            np.float32)
        self.done = np.full(len(self.targets), np.nan)

    def _warm_shapes(self) -> None:
        """Run the ego forward once at every shape of the grid, with the
        argument types ``GNNServeEngine.tick`` passes."""
        b, d = self.prm["batch"], self.dep.feats.shape[1]
        for node_cap, arc_cap in self.shapes:
            rows = b * node_cap + 1
            out = self.engine.fwd(
                jnp.asarray(np.zeros((rows, d), np.float32)),
                jnp.asarray(np.full((arc_cap, 2), rows - 1, np.int32)),
                jnp.asarray(np.zeros(rows, np.float32)),
                jnp.asarray(np.arange(b, dtype=np.int32) * node_cap))
            jax.block_until_ready(out)

    # ------------------------------------------------------------- window
    def window(self, seconds: float, spans) -> dict:
        eng, due, targets = self.engine, self.due, self.targets
        s0 = (eng.stats.requests, eng.stats.batches, eng.stats.wall_time_s)
        count, i, served = len(due), 0, 0
        lag = np.zeros(count)
        pending: deque = deque()
        ticks = []                       # (seconds, start) of every tick
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            j = int(np.searchsorted(due, now, side="right"))
            if j > i:
                eng.submit(targets[i:j])
                pending.extend(range(i, j))
                lag[i:j] = now - due[i:j]
                i = j
            if eng.queue:
                with host_span(spans, "tick"):
                    rows = eng.tick()
                t = time.perf_counter() - t0
                ticks.append((t - now, now))
                for r in rows:
                    k = pending.popleft()
                    self.out[k], self.done[k] = r, t
                served += len(rows)
            elif i < count:
                with host_span(spans, "wait"):
                    time.sleep(min(max(due[i] - now, 0.0), seconds - now))
        wall = time.perf_counter() - t0
        st = eng.stats
        tick_s = np.array([d for d, _ in ticks] or [0.0])
        stall = tick_s > STALL_FACTOR * np.median(tick_s)
        self.window_stats = {
            "requests": st.requests - s0[0], "ticks": st.batches - s0[1],
            "tick_wall_s": st.wall_time_s - s0[2], "wall_s": wall,
            "stall_ticks": int(stall.sum()),
            "stall_s": float(tick_s[stall].sum())}
        self.attempted, self.failed = served, 0
        print(f"submission lag ms: p50 {np.percentile(lag[:i], 50) * 1e3:.4f} "
              f"p95 {np.percentile(lag[:i], 95) * 1e3:.4f} "
              f"max {lag[:i].max() * 1e3:.4f}", file=sys.stderr)
        slow = sorted(ticks, reverse=True)[:3]
        print("slowest ticks ms (at s): " + ", ".join(
            f"{d * 1e3:.3f} ({at:.3f})" for d, at in slow), file=sys.stderr)
        print(f"stalls: {self.window_stats['stall_ticks']} ticks over "
              f"{STALL_FACTOR}x the median tick, "
              f"{self.window_stats['stall_s']:.4f} s", file=sys.stderr)
        served_in_window = int((self.done <= seconds).sum())
        lat = (self.done - due)[np.isfinite(self.done)]
        print(f"latency ms: p50 {np.percentile(lat, 50) * 1e3:.4f} "
              f"p95 {np.percentile(lat, 95) * 1e3:.4f}; answered {served}, "
              f"in window {served_in_window}, due {count}", file=sys.stderr)
        return {"ego_served_rps": served_in_window / seconds}

    def counters(self) -> dict:
        return dict(self.window_stats)

    # -------------------------------------------------------- correctness
    def readings(self) -> dict:
        """Every answered request against the reference's row."""
        ref = reference.forward(self.model, self.dep.weights,
                                self.dep.feats_dev, self.dep.edges,
                                self.ref_mode)
        ok = np.isfinite(self.done)
        return check.readings(self.out[ok], ref[self.targets[ok]],
                              check.scale_of(ref))

    def release(self) -> None:
        self.engine = None
