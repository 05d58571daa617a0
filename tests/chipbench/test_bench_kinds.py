"""Model kinds as files (``bench/kinds/<kind>.py``): each kind against the
program's own parameter layout and reference forward, GCN's and SAGE's
weights and references bit for bit as the harness drew and computed them
when it named the two kinds itself (that code is kept below as the
oracle), the configured graphs and SIoT's layout as the parent made them,
and a throwaway kind that reaches the weights, the reference and the
counts as one file in another directory, and a whole configuration of it
as files alone."""
import functools
import hashlib
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import fleet, graphs, inputs, reference, registry, work  # noqa: E402

KINDS = sorted(p.stem for p in (ROOT / "bench" / "kinds").glob("*.py"))
# The oracle below implements these kinds alone; a kind of another name is
# held to the program by the tests parametrised over ``KINDS``.
ORACLE_KINDS = ("gcn", "sage")
ORACLE_CONFIGS = [c["name"] for c in registry.benchmark()["configs"]
                  if registry.load_json("configs", c["name"])["model"]["kind"]
                  in ORACLE_KINDS]
N = 300


def _small(model, dims=(8, 6, 3), precision="highest"):
    return {**model, "layer_dims": list(dims), "dtype": "float32",
            "matmul_precision": precision, "weights_seed": 0}


def _graph(n=200, links=700):
    n, edges, _ = graphs.build({"generator": "siot", "n": n, "links": links,
                                "seed": 4, "area": 10.0})
    return n, edges


# --------------------------------------------------------------- the oracle
# The harness's weights and reference as they were written before the kinds
# moved into files, kept verbatim apart from names.
def _oracle_weight_shapes(model: dict) -> list:
    dims = model["layer_dims"]
    wide = 2 if model["kind"] == "sage" else 1
    return [(wide * dims[k], dims[k + 1]) for k in range(len(dims) - 1)]


def _oracle_make(model: dict, n: int, seed: int):
    shapes = _oracle_weight_shapes(model)
    d0 = model["layer_dims"][0]

    def draw(key, wkey):
        feats = jax.random.normal(key, (n, d0), jnp.float32)
        weights = []
        for k, (fi, fo) in zip(jax.random.split(wkey, len(shapes)), shapes):
            lim = (6.0 / (fi + fo)) ** 0.5
            weights.append({"w": jax.random.uniform(k, (fi, fo), jnp.float32,
                                                    -lim, lim)})
        return feats, weights

    keys = (inputs.key_of(seed), inputs.key_of(model["weights_seed"]))
    return jax.jit(draw)(*keys)


@functools.partial(jax.jit, static_argnames=("kind", "n", "dtype",
                                             "precision"))
def _oracle_forward_jit(weights, feats, src, dst, *, kind, n, dtype,
                        precision):
    dt = jnp.dtype(dtype)
    prec = jax.lax.Precision.HIGHEST if precision == "highest" else None
    deg = jax.ops.segment_sum(jnp.ones(src.shape, dt), dst, num_segments=n)
    h = feats.astype(dt)
    last = len(weights) - 1
    for k, layer in enumerate(weights):
        w = layer["w"].astype(dt)
        agg = jax.ops.segment_sum(h[src], dst, num_segments=n)
        if kind == "gcn":
            z = (agg + h) / (deg + 1)[:, None]
        elif kind == "sage":
            z = jnp.concatenate([agg / jnp.maximum(deg, 1)[:, None], h], -1)
        else:
            raise ValueError(kind)
        out = jnp.dot(z, w, precision=prec, preferred_element_type=dt)
        h = out if k == last else jnp.maximum(out, 0)
    return h


def _oracle_forward(kind, weights, feats, edges, mode):
    n = int(feats.shape[0])
    src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int32)
    dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int32)
    out = _oracle_forward_jit(weights, jnp.asarray(feats), jnp.asarray(src),
                              jnp.asarray(dst), kind=kind, n=n,
                              dtype=mode[0], precision=mode[1])
    return np.asarray(out, dtype=np.float32)


# ------------------------------------------------------------------- tests
@pytest.mark.parametrize("kind", KINDS)
def test_weight_layout_is_the_programs(kind):
    from repro.gnn import GNNConfig, init_params

    model = _small({"kind": kind})
    cfg = GNNConfig(kind, tuple(model["layer_dims"]),
                    **registry.kind(kind).config_fields(model))
    program = init_params(jax.random.PRNGKey(0), cfg)
    _, weights = inputs.make(model, 5, 1)
    assert jax.tree.structure(weights) == jax.tree.structure(program)
    assert [x.shape for x in jax.tree.leaves(weights)] == \
        [x.shape for x in jax.tree.leaves(program)]


@pytest.mark.parametrize("kind", KINDS)
def test_kind_reference_matches_the_programs(kind):
    from repro.gnn import GNNConfig, reference_forward

    model = _small({"kind": kind})
    n, edges = _graph()
    feats, weights = inputs.make(model, n, 2 ** 31 + 3)
    ours = reference.forward(model, weights, feats, edges,
                             reference.modes(model)[0])
    cfg = GNNConfig(kind, tuple(model["layer_dims"]),
                    **registry.kind(kind).config_fields(model))
    theirs = reference_forward(cfg, weights, np.asarray(feats), edges)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("config", ORACLE_CONFIGS)
def test_weights_and_references_are_the_parents_bit_for_bit(config):
    cfg = registry.load_json("configs", config)
    g = cfg["graph"]
    links = int(round(g["links"] * N / g["n"]))
    n, edges, _ = graphs.build({**g, "n": N, "links": links})
    model = cfg["model"]
    for seed in (1, 2 ** 31 + 17):
        feats, weights = inputs.make(model, n, seed)
        o_feats, o_weights = _oracle_make(model, n, seed)
        assert np.array_equal(feats, o_feats)
        assert jax.tree.structure(weights) == jax.tree.structure(o_weights)
        for a, b in zip(jax.tree.leaves(weights), jax.tree.leaves(o_weights)):
            assert np.array_equal(a, b)
        for mode in reference.modes(model):
            got = reference.forward(model, weights, feats, edges, mode)
            want = _oracle_forward(model["kind"], o_weights, o_feats, edges,
                                   mode)
            assert np.array_equal(got, want), mode


@pytest.mark.parametrize("config,digest", [("siot-gcn", "a1cdfd9b6d216f86"),
                                           ("yelp-sage", "17a66f5d3b724aab")])
def test_generated_graphs_are_the_parents(config, digest):
    # sha256 of the int64 edge list that the generators gave when they were
    # functions of harness/graphs.py, at the published size
    n, edges, _ = graphs.build(registry.load_json("configs", config)["graph"])
    assert hashlib.sha256(edges.astype(np.int64).tobytes()).hexdigest()[:16] \
        == digest


class _Priced(Exception):
    pass


@pytest.mark.parametrize("config", ["siot-gcn", "yelp-sage"])
def test_layout_costs_the_parents_workload(config, monkeypatch):
    # the workload the layout hands the cost model at four parts is the
    # one it handed before it read the configuration's widths: the cost
    # model's default 2-layer entry, which for [d, 16, 2] is the config's
    import repro.core

    cfg = registry.load_json("configs", config)
    priced = []

    def cost_model(net, g, wl):
        priced.append(wl)
        raise _Priced

    monkeypatch.setattr(repro.core, "CostModel", cost_model)
    with pytest.raises(_Priced):
        fleet.layout(cfg, fleet.build(cfg, 1, None), 4)
    m = cfg["model"]
    assert priced == [repro.core.workload_for(m["kind"], m["layer_dims"][0])]


def test_siot_assignment_at_four_parts_is_the_parents():
    # the parent's layout: GLAD-S costed by the kind's default entry
    from repro.core import CostModel, workload_for
    from repro.core.glad_s import glad_s

    cfg = registry.load_json("configs", "siot-gcn")
    dep = fleet.build(cfg, 1, None)
    net, plan = fleet.layout(cfg, dep, 4)
    parent = glad_s(CostModel(net, dep.graph, workload_for("gcn", 52)),
                    seed=int(cfg["graph"]["seed"]))
    assert np.array_equal(plan.assign, parent.assign)


TOY = '''"""A throwaway kind: the mean of the neighbours and the vertex itself,
times ``w``, plus a bias ``b``."""
import jax
import jax.numpy as jnp


def weights(model):
    dims = model["layer_dims"]
    return [{"w": ((a, b), a, b), "b": ((b,), b, 1)}
            for a, b in zip(dims[:-1], dims[1:])]


def layer(model, k, p, h, g, dt, prec):
    agg = jax.ops.segment_sum(h[g.src], g.dst, num_segments=g.n)
    z = (agg + h) / (g.deg + 1)[:, None]
    return jnp.dot(z, p["w"].astype(dt), precision=prec,
                   preferred_element_type=dt) + p["b"].astype(dt)


def flops(model, n, arcs):
    dims = model["layer_dims"]
    return sum(arcs * a + 2 * n * a + 2 * n * a * b + n * b
               for a, b in zip(dims[:-1], dims[1:]))


def bytes(model, n, arcs):
    dims = model["layer_dims"]
    return sum(4 * (n * a + n * b + a * b + b + n) + 8 * arcs
               for a, b in zip(dims[:-1], dims[1:]))


def config_fields(model):
    return {"dtype": jnp.float32}
'''


def test_a_new_kind_is_one_file(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(TOY)
    monkeypatch.setattr(registry, "KINDS", tmp_path)
    model = _small({"kind": "toy"}, dims=(4, 3))
    n, edges = _graph(40, 90)
    feats, weights = inputs.make(model, n, 9)
    assert [sorted(p) for p in weights] == [["b", "w"]]
    w, b = np.asarray(weights[0]["w"]), np.asarray(weights[0]["b"])
    assert w.shape == (4, 3) and b.shape == (3,)
    assert np.abs(b).max() <= (6.0 / 4) ** 0.5 and b.std() > 0
    out = reference.forward(model, weights, feats, edges,
                            reference.modes(model)[0])
    x = np.asarray(feats, np.float64)
    agg = x.copy()
    deg = np.ones(n)
    for u, v in edges:
        agg[u] += x[v]
        agg[v] += x[u]
        deg[u] += 1
        deg[v] += 1
    np.testing.assert_allclose(out, (agg / deg[:, None]) @ w + b,
                               rtol=1e-5, atol=1e-5)
    arcs = 2 * len(edges)
    assert work.model_flops(model, n, arcs) == \
        arcs * 4 + 2 * n * 4 + 2 * n * 4 * 3 + n * 3
    assert work.model_bytes(model, n, arcs) == \
        4 * (n * 4 + n * 3 + 4 * 3 + 3 + n) + 8 * arcs
    assert registry.kind("toy").config_fields(model) == {"dtype": jnp.float32}


def test_a_new_config_of_a_new_kind_is_files_only(tmp_path, monkeypatch):
    """A three-layer configuration of the throwaway kind and a refresh cell
    of it, added as files beside copies of the mixes: set-up, the layout at
    one and four partitions, the reference, the control, the counts and
    3-hop ego sizes, with no harness file edited."""
    import control
    import repro.core

    shutil.copytree(ROOT / "bench" / "traffic", tmp_path / "traffic")
    for d in ("kinds", "configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "kinds" / "toy.py").write_text(TOY)
    dims = [52, 64, 64, 8]
    config = {"name": "toy3", "reduced": [],
              "graph": {"generator": "siot", "n": N, "links": 1256,
                        "feat_dim": dims[0], "seed": 0, "area": 10.0},
              "model": {"kind": "toy", "layer_dims": dims,
                        "dtype": "float32", "matmul_precision": "default",
                        "weights_seed": 0},
              "fleet": {"servers": 4, "mu_factor": 5.0, "slack": 0.5}}
    (tmp_path / "configs" / "toy3.json").write_text(json.dumps(config))
    (tmp_path / "workloads" / "toy3.refresh-1chip.json").write_text(
        json.dumps({"config": "toy3", "traffic": "refresh-1chip", "chips": 1,
                    "limits": {"max_err": 3e-3, "mean_err": 3e-5}}))
    monkeypatch.setattr(registry, "BENCH", tmp_path)
    monkeypatch.setattr(registry, "KINDS", tmp_path / "kinds")
    costed, priced = [], []
    cost_model, workload_for = repro.core.CostModel, repro.core.workload_for
    monkeypatch.setattr(repro.core, "CostModel", lambda net, g, wl: (
        costed.append(wl) or cost_model(net, g, wl)))
    # the program's cost model names its own kinds alone; the toy is priced
    # at GCN's per-term scales
    monkeypatch.setattr(repro.core, "workload_for", lambda kind, d0: (
        priced.append((kind, d0)) or workload_for("gcn", d0)))

    cell = registry.cell("toy3.refresh-1chip")
    model = cell["config"]["model"]
    dep = fleet.build(cell["config"], 2 ** 31 + 5, None)
    n, arcs = dep.n, 2 * len(dep.edges)
    assert dep.model.layer_dims == tuple(dims)
    assert [sorted(p) for p in dep.weights] == [["b", "w"]] * 3
    for parts in (1, 4):
        _, plan = fleet.layout(cell["config"], dep, parts)
        assert plan.num_parts == parts
        assert np.array_equal(np.sort(plan.local[plan.local_mask]),
                              np.arange(n))
    (wl,) = costed                       # GLAD-S ran once, at four parts
    assert priced == [("toy", dims[0])]
    assert tuple(wl.layer_dims) == tuple(dims)
    assert (wl.agg_scale, wl.upd_scale, wl.act_scale) == (1.0, 1.0, 1.0)

    out = reference.forward(model, dep.weights, dep.feats_dev, dep.edges,
                            reference.modes(model)[0])
    src, dst = graphs.directed(dep.edges)
    deg = np.bincount(dst, minlength=n) + 1.0
    h = np.asarray(dep.feats, np.float64)
    for p in dep.weights:
        agg = h.copy()
        np.add.at(agg, dst, h[src])
        h = (agg / deg[:, None]) @ np.asarray(p["w"], np.float64) \
            + np.asarray(p["b"], np.float64)
    np.testing.assert_allclose(out, h, rtol=1e-4, atol=1e-4)

    got = control.control_readings(cell, 7, 1.0)
    assert set(got) == {"max_err", "mean_err"}
    assert all(np.isfinite(v) and v > 0 for v in got.values())

    pairs = list(zip(dims[:-1], dims[1:]))
    assert work.model_flops(model, n, arcs) == sum(
        arcs * a + 2 * n * a + 2 * n * a * b + n * b for a, b in pairs)
    assert work.model_bytes(model, n, arcs) == sum(
        4 * (n * a + n * b + a * b + b + n) + 8 * arcs for a, b in pairs)

    nodes2, arcs2 = graphs.ego_sizes(n, dep.edges, 2)
    nodes3, arcs3 = graphs.ego_sizes(n, dep.edges, 3)
    assert (nodes3 >= nodes2).all() and (nodes3 > nodes2).any()
    assert (arcs3 >= arcs2).all() and (nodes3 <= n).all()


def test_an_unknown_kind_is_refused_by_name():
    with pytest.raises(ValueError, match="nope"):
        inputs.make({"kind": "nope", "layer_dims": [2, 2], "weights_seed": 0},
                    3, 1)
    with pytest.raises(ValueError, match="nope"):
        work.model_flops({"kind": "nope", "layer_dims": [2, 2]}, 3, 4)
    with pytest.raises(ValueError, match="nope"):
        work.model_bytes({"kind": "nope", "layer_dims": [2, 2]}, 3, 4)
