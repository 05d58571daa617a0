"""Model kinds as files (``bench/kinds/<kind>.py``): each kind against the
program's own parameter layout and reference forward, GCN's and SAGE's
weights and references bit for bit as the harness drew and computed them
when it named the two kinds itself (that code is kept below as the
oracle), and a throwaway kind that reaches the weights, the reference and
the counts as one file in another directory."""
import functools
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import graphs, inputs, reference, registry, work  # noqa: E402

KINDS = sorted(p.stem for p in (ROOT / "bench" / "kinds").glob("*.py"))
CONFIGS = [c["name"] for c in registry.benchmark()["configs"]]
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


@pytest.mark.parametrize("config", CONFIGS)
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
    assert registry.kind("toy").config_fields(model) == {"dtype": jnp.float32}


def test_an_unknown_kind_is_refused_by_name():
    with pytest.raises(ValueError, match="nope"):
        inputs.make({"kind": "nope", "layer_dims": [2, 2], "weights_seed": 0},
                    3, 1)
    with pytest.raises(ValueError, match="nope"):
        work.model_flops({"kind": "nope", "layer_dims": [2, 2]}, 3, 4)
