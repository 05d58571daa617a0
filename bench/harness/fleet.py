"""Set-up shared by every driver: the configuration's graph, the run's
features and weights, and the program's layout of the graph over the
fleet (``build_edge_network`` -> GLAD-S -> ``compile_plan``)."""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import graphs, inputs, registry


@dataclasses.dataclass
class Deployment:
    n: int
    edges: np.ndarray          # (E, 2) canonical links
    feats_dev: object          # (n, d_0) on the first device
    feats: np.ndarray          # the same on the host
    weights: list              # per layer, the kind's leaves, on the first device
    graph: object              # the program's DataGraph
    model: object              # the program's GNNConfig


def build(config: dict, seed: int, device) -> Deployment:
    from repro.gnn import GNNConfig
    from repro.graphs.datagraph import DataGraph

    n, edges, coords = graphs.build(config["graph"])
    feats_dev, weights = inputs.make(config["model"], n, seed, device)
    feats = np.asarray(feats_dev)
    g = DataGraph(n=n, edges=edges, features=feats, coords=coords)
    m = config["model"]
    fields = registry.kind(m["kind"]).config_fields(m)
    return Deployment(n=n, edges=edges, feats_dev=feats_dev, feats=feats,
                      weights=weights, graph=g,
                      model=GNNConfig(m["kind"], tuple(m["layer_dims"]),
                                      **fields))


def layout(config: dict, dep: Deployment, parts: int):
    """(EdgeNetwork, ShardPlan).  ``parts == 1`` keeps every vertex on one
    partition, as a single server holds the whole graph.  Otherwise GLAD-S
    costs the configuration's own ``layer_dims``, with the per-term scales
    of the kind's entry in the program's cost model (``workload_for``)."""
    from repro.core import CostModel, workload_for
    from repro.core.glad_s import glad_s
    from repro.core.partition import partition_from_assign
    from repro.gnn import compile_plan
    from repro.graphs import build_edge_network

    fl, g = config["fleet"], dep.graph
    seed = int(config["graph"]["seed"])
    net = build_edge_network(g, int(fl["servers"]), seed=seed,
                             mu_factor=float(fl["mu_factor"]))
    if parts == 1:
        part = partition_from_assign(g, np.zeros(g.n, np.int64), 1, {})
    else:
        if parts != int(fl["servers"]):
            raise ValueError(f"{parts} partitions over "
                             f"{fl['servers']} servers")
        m = config["model"]
        dims = list(m["layer_dims"])
        scales = workload_for(m["kind"], dims[0])
        cm = CostModel(net, g, dataclasses.replace(scales, layer_dims=dims))
        res = glad_s(cm, seed=seed)
        part = partition_from_assign(g, res.assign, parts, res.factors)
    return net, compile_plan(g, part, slack=float(fl["slack"]))
