"""Request-driven GNN serving over the live ShardPlan (paper Sec. II-A).

Everything else in the repo is whole-graph BSP forward; the paper's target
workload is a RESIDENT SERVICE answering streams of per-user requests, each
touching only the small k-hop ego-subgraph of its target vertex (the
Fograph scenario).  This module is that request path:

  * :func:`extract_ego` / :func:`extract_ego_batch` — batched k-hop
    ego-subgraph extraction against the partitioned graph with STATIC
    shapes: fixed fanout per hop, node/arc counts padded to power-of-2
    buckets (the graphbolt ``neighbor_sampler`` idiom), so the jitted
    forward traces O(log) specializations instead of one per request.
    Both are one BFS over every target of a batch at once (one
    ``csr_multirange`` per hop over the whole (request, vertex)
    frontier), costing the egos' total size and no Python loop per
    request; each request keeps the per-target order — target first,
    then each depth's new vertices in ascending id; arcs by hop, then
    destination in ascending id, then CSR order.
  * :func:`make_ego_forward` — the batched ego inference, reusing the
    EXACT layer functions of :mod:`repro.gnn.models`.  With full fanout
    the target rows reproduce the whole-graph forward within f32
    reduction-order tolerance (see the function docstring): extraction
    keeps every node's incoming arcs in ascending-neighbor order, the
    same per-destination float summation order as ``directed_edges``
    (both reduce to the CSR neighbor order), and full-graph degrees ride
    in as data.
    Depth-``hops`` nodes contribute raw features only — their own
    (truncated) aggregations never reach the target row.
  * :class:`FeatureCache` — per-server cache of remote feature rows with
    hot-vertex admission, mirroring the layout engine's TinyLFU-lite
    ``_admit`` discipline (AssemblyCache): under budget pressure a row is
    admitted only when touched >= 2 times and strictly more often than
    the LRU victim; halo-seeded rows are resident from the start.
  * :class:`GNNServeEngine` — queue -> batch -> extract -> forward ticks
    over the LIVE plan: homes come from ``plan.assign`` at tick time and
    caches re-seed when ``plan.version`` moves, so a fault-runtime
    ``patch_plan`` mid-stream keeps the service answering.  Reports
    throughput and p50/p99 latency under (Zipf-skewed) request streams.
  * :func:`zipf_requests` / :func:`request_traffic` /
    :func:`serving_cost` — skewed streams, the (optionally ego-propagated)
    requests/vertex histogram that feeds ``CostModel(traffic=...)`` (the
    paper's traffic-weighted unary compute row), and the analytic
    per-request serving cost under distributed ego execution that
    compares traffic-aware vs traffic-blind layouts.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.gnn.distributed import ShardPlan
from repro.gnn.models import _LAYERS, GNNConfig, segment_sum
from repro.graphs.datagraph import DataGraph, csr_multirange


def _pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


# ------------------------------------------------------------ request streams
def zipf_requests(n: int, num_requests: int, s: float = 1.1,
                  seed: int = 0) -> np.ndarray:
    """Zipf-skewed request targets: vertex popularity follows rank^-s over
    a seeded random rank permutation (the hot set is not id-correlated)."""
    rng = np.random.default_rng(seed)
    ranks = rng.permutation(n)
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    p = np.empty(n, dtype=np.float64)
    p[ranks] = w / w.sum()
    return rng.choice(n, size=num_requests, p=p).astype(np.int64)


def request_traffic(n: int, targets: np.ndarray, smooth: float = 0.0,
                    graph: Optional[DataGraph] = None,
                    hops: int = 0) -> np.ndarray:
    """Traffic weights for ``CostModel(traffic=...)``, normalized to MEAN 1.

    With ``graph``/``hops``, each request's count propagates to every
    vertex of its ``hops``-ego — the number of request egos that TOUCH a
    vertex, which is exactly the weight its compute row carries under
    distributed ego execution (see :func:`serving_cost`).  Without, it is
    the plain requests/target histogram.  Mean-1 normalization keeps the
    traffic-aware C_P on the same scale as the blind one, so aware and
    blind layout costs stay comparable.  ``smooth`` adds a uniform floor
    (cold vertices keep a nonzero compute row)."""
    targets = np.asarray(targets, dtype=np.int64)
    if graph is not None and hops > 0:
        counts = np.zeros(n, dtype=np.float64)
        uniq, cnt = np.unique(targets, return_counts=True)
        for v, c in zip(uniq, cnt):
            nodes, _, _ = extract_ego(graph, int(v), hops)
            counts[nodes] += float(c)
    else:
        counts = np.bincount(targets, minlength=n).astype(np.float64)
    counts += float(smooth)
    mean = counts.mean()
    return counts / mean if mean > 0 else np.ones(n)


def link_traffic(graph: DataGraph, targets: np.ndarray, hops: int,
                 fanout: Optional[int] = None,
                 smooth: float = 0.0) -> np.ndarray:
    """Per-LINK ego-crossing histogram, mean-1 normalized — the edge-weight
    side of a traffic-aware layout.

    A request's remote ego rows are fetched across the links its ego
    spans, so the number of request egos containing a link is the weight
    its cut cost carries under serving.  Feed the product
    ``graph.weights_or_ones() * link_traffic(...)`` into a graph copy
    (``dataclasses.replace(graph, edge_weights=...)``) and GLAD's pairwise
    C_T term prices exactly that: hot neighborhoods get pulled onto one
    server, which is what the fetch term of :func:`serving_cost` rewards.
    (The unary side is :func:`request_traffic`; the serving bench composes
    both.)"""
    e = graph.edges
    counts = np.zeros(len(e), dtype=np.float64)
    if len(e):
        keys = e[:, 0] * graph.n + e[:, 1]            # canonical lo < hi
        order = np.argsort(keys)
        skeys = keys[order]
        uniq, cnt = np.unique(np.asarray(targets, dtype=np.int64),
                              return_counts=True)
        for v, c in zip(uniq, cnt):
            _, arcs, _ = extract_ego(graph, int(v), hops, fanout)
            if not len(arcs):
                continue
            k = arcs.min(axis=1) * graph.n + arcs.max(axis=1)
            eids = np.unique(order[np.searchsorted(skeys, k)])
            counts[eids] += float(c)
    counts += float(smooth)
    mean = counts.mean()
    return counts / mean if mean > 0 else np.ones(len(e))


# ------------------------------------------------------------- ego extraction
def _ego_walk(graph: DataGraph, targets: np.ndarray, hops: int,
              fanout: Optional[int] = None):
    """One BFS over the ``hops``-egos of every target at once.

    The frontier is a set of (request, vertex) pairs, each depth's kept as
    its sorted ``request * n + vertex`` keys, so a vertex reached by two
    requests is new in both and the state is the size of the egos.  Each
    hop gathers the whole frontier's CSR rows in one
    :func:`csr_multirange`, caps each row at its first ``fanout`` entries,
    and finds the new pairs by one ``np.unique`` of the hop's keys and a
    ``searchsorted`` into each earlier depth.

    Returns flat arrays in WALK order, depth by depth and, within a depth,
    by (request, vertex): the nodes as ``(req, vertex, depth)`` and the
    arcs as ``(req, src, dst)``, ``src``/``dst`` being walk indices into
    the nodes.  A hop's arcs run by request, then frontier vertex in
    ascending id, then CSR order.  With one target, walk order is the
    ego's own order."""
    indptr, indices, n = graph.indptr, graph.indices, graph.n
    fv = np.asarray(targets, dtype=np.int64)
    fr = np.arange(len(fv), dtype=np.int64)
    levels = [fr * n + fv]                 # sorted keys of each depth
    areqs, srcs, dsts = [], [], []
    first, total = 0, len(fv)              # walk index of frontier, of end
    for _ in range(hops):
        if not len(fv):
            break
        flat, rep = csr_multirange(indptr, fv)
        nbrs = indices[flat].astype(np.int64)
        if fanout is not None and len(nbrs):
            counts = indptr[fv + 1] - indptr[fv]
            within = (np.arange(len(flat))
                      - np.repeat(np.cumsum(counts) - counts, counts))
            keep = within < fanout
            nbrs, rep = nbrs[keep], rep[keep]
        r = fr[rep]
        keys, inv = np.unique(r * n + nbrs, return_inverse=True)
        at = np.full(len(keys), -1, dtype=np.int64)    # walk index per key
        base = 0
        for lvl in levels:
            if len(lvl):
                pos = np.searchsorted(lvl, keys)
                pos[pos == len(lvl)] = 0
                hit = lvl[pos] == keys
                at[hit] = base + pos[hit]
            base += len(lvl)
        fresh = at < 0
        new = keys[fresh]
        at[fresh] = total + np.arange(len(new))
        areqs.append(r)
        srcs.append(at[inv])
        dsts.append(first + rep)
        first, total = total, total + len(new)
        levels.append(new)
        fr, fv = new // n, new % n

    keys = np.concatenate(levels)
    depth = np.repeat(np.arange(len(levels)), [len(k) for k in levels])

    def cat(parts):
        return np.concatenate(parts) if parts else np.zeros(0, np.int64)

    return (keys // n, keys % n, depth), (cat(areqs), cat(srcs), cat(dsts))


def extract_ego(graph: DataGraph, target: int, hops: int,
                fanout: Optional[int] = None):
    """k-hop ego subgraph of ``target``: (nodes, arcs, depth).

    ``nodes`` (global ids, ``nodes[0] == target``) are the vertices within
    ``hops``: the target, then each depth's new vertices in ascending id;
    ``depth`` is each node's hop count.  ``arcs`` (global (src, dst)) are
    ALL incoming arcs of every node at depth < hops — exactly what a
    ``hops``-layer GNN needs to reproduce the whole-graph output at the
    target (depth-``hops`` nodes contribute raw features only, so they
    carry no arcs) — grouped by hop, then by destination in ascending id,
    each destination's arcs in ascending src (CSR) order: the same
    summation order as the full-graph ``directed_edges`` path, which keeps
    the ego forward within f32 reduction-order tolerance of the
    whole-graph forward.  ``fanout`` truncates each node's neighbor list to
    its first ``fanout`` entries (ascending-id prefix — deterministic
    sampling; ``None`` / >= max degree is exact).  This is the batched walk
    of :func:`extract_ego_batch` with one target."""
    (_, nodes, depth), (_, src, dst) = _ego_walk(graph, [target], hops,
                                                  fanout)
    return nodes, np.stack([nodes[src], nodes[dst]], axis=1), depth


@dataclasses.dataclass
class EgoBatch:
    """Flattened disjoint union of B ego subgraphs, bucket-padded.

    Local flat id of request b's i-th node is ``b * node_cap + i`` (target
    always slot 0); ``arcs`` pads point at the ``dummy`` row, whose
    aggregation lands in a segment the forward slices off."""

    nodes: np.ndarray        # (B, node_cap) global ids, -1 pad
    arcs: np.ndarray         # (arc_cap, 2) int32 LOCAL flat (src, dst)
    targets: np.ndarray      # (B,) global ids, -1 = empty slot
    num_nodes: np.ndarray    # (B,) real nodes per request
    num_arcs: int            # real arcs (before bucket padding)
    hops: int
    fanout: Optional[int]

    @property
    def batch(self) -> int:
        return int(self.nodes.shape[0])

    @property
    def node_cap(self) -> int:
        return int(self.nodes.shape[1])

    @property
    def dummy(self) -> int:
        return self.batch * self.node_cap


def extract_ego_batch(graph: DataGraph, targets: np.ndarray, hops: int,
                      fanout: Optional[int] = None,
                      batch: Optional[int] = None) -> EgoBatch:
    """Batched extraction with jit-stable shapes: ``node_cap`` (per-request
    node slots) and the arc count are padded to power-of-2 buckets, and the
    batch dimension to ``batch`` (short final batches pad with empty
    requests, target -1).

    One walk (:func:`_ego_walk`) extracts every request's ego at once; its
    cost grows with the egos' total size, not with the number of requests.
    Each request gets exactly :func:`extract_ego`'s nodes and arcs, in its
    order (a repeated target gets its own identical ego; an isolated one a
    single node and no arcs): a stable sort by request turns the walk's
    depth-major order into each request's, and since the walk names each
    arc's ends by node index, arcs reach their local slots by a gather."""
    targets = np.asarray(targets, dtype=np.int64)
    B = int(batch) if batch is not None else len(targets)
    if len(targets) > B:
        raise ValueError(f"{len(targets)} targets > batch {B}")
    (req, verts, _), (areq, src, dst) = _ego_walk(graph, targets, hops,
                                                  fanout)
    num_nodes = np.bincount(req, minlength=B)
    node_cap = _pow2(num_nodes.max(initial=1))
    arc_cap = _pow2(max(len(src), 1))
    # Walk index -> local flat id b * node_cap + slot, slot = position in
    # the request's own order.
    order = np.argsort(req, kind="stable")
    first = np.cumsum(num_nodes) - num_nodes
    rs = req[order]
    local = np.empty(len(req), dtype=np.int64)
    local[order] = rs * node_cap + np.arange(len(req)) - first[rs]
    nodes = np.full((B, node_cap), -1, dtype=np.int64)
    nodes.reshape(-1)[local] = verts
    dummy = B * node_cap
    arcs = np.full((arc_cap, 2), dummy, dtype=np.int32)
    order = np.argsort(areq, kind="stable")
    arcs[: len(order), 0] = local[src[order]]
    arcs[: len(order), 1] = local[dst[order]]
    tgt = np.full(B, -1, dtype=np.int64)
    tgt[: len(targets)] = targets
    return EgoBatch(nodes=nodes, arcs=arcs, targets=tgt,
                    num_nodes=num_nodes, num_arcs=len(order), hops=hops,
                    fanout=fanout)


def ego_tables(ego: EgoBatch, features: np.ndarray, degrees: np.ndarray):
    """Device-ready arrays for an EgoBatch: the flattened feature table
    (dummy zero row last), FULL-GRAPH degree per slot (GCN/SAGE normalize
    by true degree, never by the sampled arc count), and the target rows
    (slot 0 of every request)."""
    d = features.shape[1]
    flat = np.zeros((ego.dummy + 1, d), dtype=features.dtype)
    valid = ego.nodes >= 0
    vflat = valid.reshape(-1)
    flat[: ego.dummy][vflat] = features[ego.nodes[valid]]
    deg = np.zeros(ego.dummy + 1, dtype=np.float32)
    deg[: ego.dummy][vflat] = degrees[ego.nodes[valid]]
    tgt_rows = (np.arange(ego.batch) * ego.node_cap).astype(np.int32)
    return flat, deg, tgt_rows


# -------------------------------------------------------------- ego inference
def make_ego_forward(cfg: GNNConfig, params, jit: bool = True):
    """Jitted batched ego forward: (feats (dummy+1, s_0), arcs, deg,
    tgt_rows) -> (B, s_K) embeddings at the targets.

    Runs the UNMODIFIED layer functions of :mod:`repro.gnn.models` over the
    flattened union graph, so semantics (and, with full fanout, bits) match
    the whole-graph forward at the target rows.  ``fwd.stats['traces']``
    counts jit traces (incremented at trace time — the make_bsp_forward
    contract): bucketed shapes bound it by O(log) per dimension.  The
    jitted program is named ``jit__fwd`` in compiled text and in profiler
    traces; ``fwd.lower(*args)`` lowers it without running it.

    ``jit=False`` runs the same program eagerly.  Agreement with the
    whole-graph reference (:func:`repro.gnn.models.reference_forward`,
    ``highest`` matmul precision) is a tolerance, never bit equality,
    because no backend promises that a matmul row's bits are independent
    of the matrix height:

      * XLA CPU (f32) — every model within f32 reduction-order tolerance
        (rtol 1e-5, atol 1e-6; tests/test_serving.py): the ego table has
        another height than the whole graph, XLA tiles a dot by its shape,
        and under jit it may split SAGE's ``[agg, h] @ w`` into two
        partial matmuls;
      * TPU (default precision) — matmuls round their f32 operands to
        bf16, so rows sit within bf16 rounding of the reference; the
        tolerance is stated where it is checked (``chip_smoke.py``)."""
    state = {"traces": 0}
    layer_fn = _LAYERS[cfg.model]
    K = cfg.num_layers

    def _fwd(feats, arcs, deg, tgt_rows):
        state["traces"] += 1             # python body runs once per trace
        n = feats.shape[0]
        h = feats.astype(cfg.dtype)
        for k, p in enumerate(params):
            h = layer_fn(p, h, arcs, deg, n, k == K - 1, segment_sum)
        return h[tgt_rows]

    jfn = jax.jit(_fwd) if jit else _fwd

    def fwd(feats, arcs, deg, tgt_rows):
        return jfn(feats, arcs, deg, tgt_rows)

    fwd.stats = state
    if jit:
        fwd.lower = jfn.lower
    return fwd


# ---------------------------------------------------------------- feature DB
class FeatureCache:
    """Per-server cache of REMOTE feature rows under a byte budget.

    Admission/eviction mirror the layout engine's AssemblyCache exactly
    (TinyLFU-lite + LRU): under budget pressure a fetched row is admitted
    only when it has been touched at least twice AND strictly more often
    than the LRU victim plus one (the engine's anti-thrash margin); rows
    seeded resident (the plan's halo — they ARE the server's read set)
    bypass admission like the engine's proven-hot rebuilds."""

    def __init__(self, row_bytes: int, cache_bytes: int):
        self.row_bytes = max(int(row_bytes), 1)
        self.cache_bytes = int(cache_bytes)
        self._rows: "OrderedDict[int, None]" = OrderedDict()
        self._touches: Dict[int, int] = {}
        self._used = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.rejected = 0

    @property
    def resident(self) -> int:
        return len(self._rows)

    def seed(self, ids: np.ndarray) -> None:
        """Install rows as resident (halo seeding) — bypasses admission."""
        for v in np.asarray(ids, dtype=np.int64):
            v = int(v)
            if v not in self._rows:
                self._rows[v] = None
                self._used += self.row_bytes
        self._evict()

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        """Touch every id; True where resident (hit refreshes LRU)."""
        hit = np.zeros(len(ids), dtype=bool)
        for k, v in enumerate(np.asarray(ids, dtype=np.int64)):
            v = int(v)
            self._touches[v] = self._touches.get(v, 0) + 1
            if v in self._rows:
                self._rows.move_to_end(v)
                hit[k] = True
        nh = int(hit.sum())
        self.hits += nh
        self.misses += len(ids) - nh
        return hit

    def admit(self, ids: np.ndarray) -> None:
        """Offer fetched rows for residency (call after a lookup miss)."""
        for v in np.asarray(ids, dtype=np.int64):
            v = int(v)
            if v in self._rows:
                continue
            if self._admit(self._touches.get(v, 0)):
                self._rows[v] = None
                self._used += self.row_bytes
                self._evict()
            else:
                self.rejected += 1

    def _admit(self, touches: int) -> bool:
        if not self._rows or self._used + self.row_bytes <= self.cache_bytes:
            return True
        if touches < 2:
            return False
        victim = next(iter(self._rows))
        return touches > self._touches.get(victim, 0) + 1

    def _evict(self) -> None:
        while self._used > self.cache_bytes and len(self._rows) > 1:
            self._rows.popitem(last=False)
            self._used -= self.row_bytes
            self.evictions += 1


# ------------------------------------------------------------- serving engine
@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    batches: int = 0
    wall_time_s: float = 0.0
    local_rows: int = 0          # ego rows owned by the home server
    replica_hit_rows: int = 0    # remote rows resident as plan replicas
    cache_hit_rows: int = 0      # remote rows served from the home's cache
    fetched_rows: int = 0        # remote rows pulled cross-server
    fetch_cost: float = 0.0      # sum tau[home, owner] over fetched rows
    plan_refreshes: int = 0      # cache re-seeds after plan.version moved
    rows: int = 0                # real ego rows the forward computed
    row_slots: int = 0           # rows it computed, padding and dummy too

    @property
    def throughput_rps(self) -> float:
        return (self.requests / self.wall_time_s
                if self.wall_time_s > 0 else 0.0)


class GNNServeEngine:
    """Resident request service over the live partitioned graph.

    Each tick pops up to ``batch`` queued targets, extracts their ego
    subgraphs, accounts feature locality against the CURRENT
    ``plan.assign`` (home = the target's server; remote rows consult the
    plan's REPLICA table first — a replica-resident row is served from the
    home's persistent copy at zero fetch — then the home's
    :class:`FeatureCache`; misses charge ``tau[home, owner]``), and runs
    the jitted batched ego forward.  The plan is read live: when
    ``plan.version`` moves (a fault-runtime ``patch_plan``), caches and
    replica masks re-seed and serving continues — no rebuild of the
    engine.  Re-seeds also SNAPSHOT the per-epoch counters: ``stats``
    stays cumulative across the engine's whole life, ``epoch_stats`` /
    ``latency_percentiles(window='epoch')`` cover only the current plan
    version (throughput/p99 after a patch must not be diluted by the old
    plan's rows — the ledger before this snapshot silently mixed plans),
    and ``epoch_history`` keeps the closed epochs.  ``hops`` defaults to
    the model depth (exact receptive field); ``fanout`` bounds per-hop
    neighbors (None = exact).

    Tracing: set ``engine.spans = []`` and every :meth:`tick` appends
    ``(name, start_ns, end_ns)`` tuples on ``time.perf_counter_ns``:
    ``serve.tick`` for the whole tick and, inside it, one after the other,
    ``serve.extract`` (popping the batch and :func:`extract_ego_batch`),
    ``serve.account`` (row accounting against the plan), ``serve.tables``
    (:func:`ego_tables`), ``serve.h2d`` (the copies to the device),
    ``serve.dispatch`` (the forward's call until it returns) and
    ``serve.fetch`` (until the output rows are on the host).  Set it back
    to ``None`` (the default) to stop recording.  ``stats.rows`` over
    ``stats.row_slots`` is the share of the forward's rows that are real
    ego rows rather than bucket padding."""

    def __init__(self, cfg: GNNConfig, params, graph: DataGraph,
                 plan: ShardPlan, features: Optional[np.ndarray] = None,
                 hops: Optional[int] = None, fanout: Optional[int] = None,
                 batch: int = 8, cache_bytes: int = 1 << 20, net=None):
        self.cfg, self.params = cfg, params
        self.graph = graph
        self.plan = plan
        feats = features if features is not None else graph.features
        if feats is None:
            raise ValueError("serving needs vertex features")
        self.features = np.asarray(feats)
        self.hops = int(hops) if hops is not None else cfg.num_layers
        self.fanout = fanout
        self.batch = int(batch)
        self.cache_bytes = int(cache_bytes)
        self.net = net                      # optional: prices fetch_cost
        self.queue: deque = deque()         # (target, t_submit)
        self.stats = ServeStats()
        self.latencies: List[float] = []
        # Per-plan-version window: reset on every cache re-seed so the
        # post-patch report covers the new plan only.
        self.epoch_stats = ServeStats()
        self.epoch_latencies: List[float] = []
        self.epoch_history: List[dict] = []
        self.spans: Optional[list] = None   # (name, start_ns, end_ns) per phase
        self.fwd = make_ego_forward(cfg, params)
        self._degrees = graph.degrees.astype(np.float32)
        self._caches: Dict[int, FeatureCache] = {}
        self._replica_mask: Dict[int, np.ndarray] = {}
        self._plan_version = -1
        self._refresh_caches()

    # ------------------------------------------------------------------ admin
    def _refresh_caches(self) -> None:
        if self._plan_version >= 0:
            self._close_epoch()
        row_bytes = self.features.shape[1] * self.features.dtype.itemsize
        self._caches = {}
        for p in range(self.plan.num_parts):
            c = FeatureCache(row_bytes, self.cache_bytes)
            halo = self.plan.halo[p]
            c.seed(halo[halo >= 0])
            self._caches[p] = c
        # Replica tier: rows the plan keeps PERSISTENTLY resident on each
        # server (read-only copies synced once per epoch, not cached
        # fetches) — consulted before the cache, never evicted.
        self._replica_mask = {}
        if getattr(self.plan, "has_replicas", False):
            for p in range(self.plan.num_parts):
                ids = self.plan.replica[p]
                m = np.zeros(self.graph.n, dtype=bool)
                m[ids[ids >= 0]] = True
                self._replica_mask[p] = m
        self._plan_version = self.plan.version

    def _close_epoch(self) -> None:
        """Archive the finished plan-version window and start a fresh one."""
        self.epoch_history.append({
            "plan_version": self._plan_version,
            "stats": self.epoch_stats,
            "latency": self.latency_percentiles(window="epoch"),
        })
        self.epoch_stats = ServeStats()
        self.epoch_latencies = []

    def cache_stats(self) -> Dict[str, int]:
        out = {"hits": 0, "misses": 0, "evictions": 0, "rejected": 0,
               "resident": 0}
        for c in self._caches.values():
            out["hits"] += c.hits
            out["misses"] += c.misses
            out["evictions"] += c.evictions
            out["rejected"] += c.rejected
            out["resident"] += c.resident
        return out

    def submit(self, targets) -> None:
        now = time.perf_counter()
        for t in np.atleast_1d(np.asarray(targets, dtype=np.int64)):
            self.queue.append((int(t), now))

    # ------------------------------------------------------------------ serve
    def _account(self, ego: EgoBatch, targets: np.ndarray) -> None:
        assign = self.plan.assign
        tau = self.net.tau if self.net is not None else None
        ledgers = (self.stats, self.epoch_stats)
        for b in range(len(targets)):
            home = int(assign[targets[b]])
            row = ego.nodes[b]
            ns = row[row >= 0]
            owners = assign[ns]
            local = owners == home
            for st in ledgers:
                st.local_rows += int(local.sum())
            remote = ns[~local]
            if not len(remote):
                continue
            rmask = self._replica_mask.get(home)
            if rmask is not None:
                rhit = rmask[remote]
                for st in ledgers:
                    st.replica_hit_rows += int(rhit.sum())
                remote = remote[~rhit]
                if not len(remote):
                    continue
            cache = self._caches[home]
            hit = cache.lookup(remote)
            for st in ledgers:
                st.cache_hit_rows += int(hit.sum())
            missed = remote[~hit]
            fc = (float(tau[home, assign[missed]].sum())
                  if tau is not None and len(missed) else 0.0)
            for st in ledgers:
                st.fetched_rows += len(missed)
                st.fetch_cost += fc
            cache.admit(missed)

    def tick(self) -> Optional[np.ndarray]:
        """Serve one batch off the queue; returns (served, s_K) embeddings
        in pop order, or None when idle."""
        if not self.queue:
            return None
        if self._plan_version != self.plan.version:
            self._refresh_caches()
            self.stats.plan_refreshes += 1
        t0 = time.perf_counter_ns()
        take = min(self.batch, len(self.queue))
        items = [self.queue.popleft() for _ in range(take)]
        targets = np.array([t for t, _ in items], dtype=np.int64)
        ego = extract_ego_batch(self.graph, targets, self.hops, self.fanout,
                                batch=self.batch)
        t1 = time.perf_counter_ns()
        self._account(ego, targets)
        t2 = time.perf_counter_ns()
        feats, deg, tgt_rows = ego_tables(ego, self.features, self._degrees)
        t3 = time.perf_counter_ns()
        args = (jnp.asarray(feats), jnp.asarray(ego.arcs), jnp.asarray(deg),
                jnp.asarray(tgt_rows))
        t4 = time.perf_counter_ns()
        out = self.fwd(*args)
        t5 = time.perf_counter_ns()
        out = np.asarray(out)
        t6 = time.perf_counter_ns()
        if self.spans is not None:
            self.spans.extend((
                ("serve.tick", t0, t6), ("serve.extract", t0, t1),
                ("serve.account", t1, t2), ("serve.tables", t2, t3),
                ("serve.h2d", t3, t4), ("serve.dispatch", t4, t5),
                ("serve.fetch", t5, t6)))
        rows, slots = int(ego.num_nodes.sum()), ego.dummy + 1
        for st in (self.stats, self.epoch_stats):
            st.wall_time_s += (t6 - t0) * 1e-9
            st.batches += 1
            st.requests += take
            st.rows += rows
            st.row_slots += slots
        now = t6 * 1e-9                  # perf_counter_ns's clock, as submit's
        for _, ts in items:
            self.latencies.append(now - ts)
            self.epoch_latencies.append(now - ts)
        return out[:take]

    def run(self, max_batches: int = 10 ** 9) -> ServeStats:
        while self.queue and self.stats.batches < max_batches:
            self.tick()
        return self.stats

    def serve(self, targets) -> np.ndarray:
        """Submit + drain synchronously; returns (len(targets), s_K)."""
        self.submit(targets)
        outs = []
        while self.queue:
            outs.append(self.tick())
        return (np.concatenate(outs, axis=0) if outs
                else np.zeros((0, self.cfg.layer_dims[-1]), np.float32))

    def latency_percentiles(self, window: str = "all") -> Dict[str, float]:
        """``window='all'``: engine lifetime; ``'epoch'``: current plan
        version only (the post-patch report)."""
        lats = self.latencies if window == "all" else self.epoch_latencies
        if not lats:
            return {"p50": 0.0, "p99": 0.0}
        arr = np.asarray(lats)
        return {"p50": float(np.percentile(arr, 50)),
                "p99": float(np.percentile(arr, 99))}


# ---------------------------------------------------------------- evaluation
def _replication_masks(replication, assign: np.ndarray, num_parts: int,
                       n: int):
    """(num_parts, n) bool of MATERIALIZED replicas (request minus homed)
    from a Replication / plain dict / replicated ShardPlan's request."""
    by_part = getattr(replication, "by_part", None)
    if by_part is None:
        by_part = getattr(replication, "replication", replication)
    mask = np.zeros((num_parts, n), dtype=bool)
    for p, ids in (by_part or {}).items():
        ids = np.asarray(ids, dtype=np.int64)
        ids = ids[(ids >= 0) & (ids < n)]
        mask[int(p), ids[assign[ids] != int(p)]] = True
    return mask


def serving_cost(cm, assign: np.ndarray, targets: np.ndarray, hops: int,
                 fanout: Optional[int] = None, replication=None,
                 sync_weight: float = 0.5, storage: float = 0.0) -> float:
    """Analytic serving cost of a layout under a request stream, under the
    paper's DISTRIBUTED execution model: each ego vertex aggregates at its
    own host (the BSP forward restricted to the ego — C_P of node ``u`` at
    ``assign[u]``), and every remotely-owned row ships its result to the
    target's home once, at ``tau[home, owner]``.  Summed over the stream,
    the compute term is exactly the ego-propagated
    :func:`request_traffic`-weighted unary compute row — the quantity a
    traffic-aware ``CostModel`` hands GLAD.

    ``replication`` (a ``core.Replication``, a ``{part: ids}`` dict, or a
    replicated ShardPlan) prices replica-resident rows at ZERO fetch —
    the copy already lives at the home, so only the one-time sync
    (``sync_weight * tau[owner, p]`` per materialized replica, the same
    rule as ``CostModel.replicate_greedy``) plus ``storage`` is charged,
    once per replica, independent of how many requests read it.  Compute
    stays at the owner — replication moves bytes, not FLOPs.

    Pass a traffic-BLIND CostModel: the stream itself carries the request
    weighting here, so a traffic-scaled ``cp_matrix`` would double count.
    This is the metric the serving bench uses to compare traffic-aware vs
    traffic-blind (and replicated vs move-only) layouts in the same
    window."""
    if cm.traffic is not None:
        raise ValueError("pass a traffic-blind CostModel (traffic=None)")
    assign = np.asarray(assign, dtype=np.int64)
    uniq, cnt = np.unique(np.asarray(targets, dtype=np.int64),
                          return_counts=True)
    cp, tau = cm.cp_matrix, cm.net.tau
    rmask = None
    total = 0.0
    if replication is not None:
        rmask = _replication_masks(replication, assign, cm.net.m,
                                   cm.graph.n)
        ps, vs = np.nonzero(rmask)
        total += float((sync_weight * tau[assign[vs], ps]).sum())
        total += storage * len(vs)
    for v, c in zip(uniq, cnt):
        nodes, _, _ = extract_ego(cm.graph, int(v), hops, fanout)
        h = int(assign[v])
        owners = assign[nodes]
        cost = float(cp[nodes, owners].sum())
        rn = nodes[owners != h]
        if rmask is not None and len(rn):
            rn = rn[~rmask[h, rn]]
        if len(rn):
            cost += float(tau[h, assign[rn]].sum())
        total += float(c) * cost
    return total


def replicate_for_stream(cm, assign: np.ndarray, targets: np.ndarray,
                         hops: int, fanout: Optional[int] = None,
                         sync_weight: float = 0.5, storage: float = 0.0,
                         budget: Optional[int] = None):
    """Serving-side move-vs-replicate greedy: pick the replica set that
    minimizes :func:`serving_cost` for THIS stream.

    ``CostModel.replicate_greedy`` weighs replicas against the layout's
    recurring halo traffic; under request serving the right weight is the
    stream itself — ``w(v, h)`` = requests homed at ``h`` whose ego
    contains remote row ``v``, each saving one ``tau[h, owner]`` fetch.
    Replicating v into h is again a unary decision given the layout:
    ``gain = w(v, h) * tau[h, owner] - (sync_weight * tau[owner, h] +
    storage)``; all positive-gain pairs are accepted (they are independent,
    so the greedy is exact for this overlay), ``budget`` caps replicas per
    part (highest gain first, id tie-break).  Returns a
    ``core.Replication`` ready for ``serving_cost(replication=...)`` /
    ``set_replication``."""
    from repro.core.cost import Replication

    if cm.traffic is not None:
        raise ValueError("pass a traffic-blind CostModel (traffic=None)")
    assign = np.asarray(assign, dtype=np.int64)
    m, n = cm.net.m, cm.graph.n
    tau = cm.net.tau
    w = np.zeros((m, n), dtype=np.float64)      # fetch multiplicity (h, v)
    uniq, cnt = np.unique(np.asarray(targets, dtype=np.int64),
                          return_counts=True)
    for v, c in zip(uniq, cnt):
        nodes, _, _ = extract_ego(cm.graph, int(v), hops, fanout)
        h = int(assign[v])
        rn = nodes[assign[nodes] != h]
        w[h, rn] += float(c)
    owner = np.broadcast_to(assign, (m, n))
    hcol = np.arange(m)[:, None]
    gain = w * tau[hcol, owner] - (sync_weight * tau[owner, hcol] + storage)
    gain = np.where(w > 0, gain, -np.inf)
    by_part, saved_t, sync_t = {}, 0.0, 0.0
    for p in range(m):
        ids = np.flatnonzero(gain[p] > 1e-12)
        if budget is not None and len(ids) > budget:
            ids = ids[np.lexsort((ids, -gain[p, ids]))[:budget]]
            ids = np.sort(ids)
        if len(ids):
            by_part[p] = ids.astype(np.int64)
            saved_t += float((w[p, ids] * tau[p, assign[ids]]).sum())
            sync_t += float((sync_weight * tau[assign[ids], p]).sum())
    count = sum(len(v) for v in by_part.values())
    stor_t = storage * count
    return Replication(by_part=by_part,
                       gain=saved_t - sync_t - stor_t, saved=saved_t,
                       sync=sync_t, storage=stor_t,
                       sync_weight=sync_weight, storage_cost=storage)
