"""Distributed BSP engine: plan invariants + simulate==oracle (+ real
shard_map collectives in a 4-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.partition import partition_from_assign
from repro.gnn.distributed import compile_plan, simulate_bsp_forward
from repro.gnn.models import GNNConfig, directed_edges, forward, init_params
from tests.conftest import random_graph


def _plan_for(g, parts, seed=0):
    assign = np.random.default_rng(seed).integers(0, parts, size=g.n)
    part = partition_from_assign(g, assign, parts, {})
    return assign, part, compile_plan(g, part)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 5000))
def test_plan_invariants(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(8, 40)), 20)
    parts = int(rng.integers(2, 5))
    assign, part, plan = _plan_for(g, parts, seed)
    # 1) every vertex appears in exactly one local block.
    seen = plan.local[plan.local >= 0]
    assert sorted(seen.tolist()) == list(range(g.n))
    # 2) every cut link's remote endpoint is in the destination's halo.
    for u, v in g.edges:
        pu, pv = assign[u], assign[v]
        if pu != pv:
            assert u in plan.halo[pv], (u, v)
            assert v in plan.halo[pu], (u, v)
    # 3) ppermute rounds deliver exactly the halo rows (no dupes/misses).
    delivered = [set() for _ in range(parts)]
    for r in plan.rounds:
        s = r["shift"]
        for p in range(parts):
            q = (p + s) % parts
            for k, li in enumerate(r["send_idx"][p]):
                if li >= 0:
                    vtx = plan.local[p, li]
                    pos = r["recv_pos"][q, k]
                    assert plan.halo[q, pos] == vtx
                    delivered[q].add(int(vtx))
    for p in range(parts):
        expect = set(plan.halo[p][plan.halo[p] >= 0].tolist())
        assert delivered[p] == expect


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_simulate_matches_full_forward(model, small_siot):
    g = small_siot
    assign, part, plan = _plan_for(g, 4, seed=1)
    cfg = GNNConfig(model, (8,) + (16, 2))
    feats = np.random.default_rng(0).normal(size=(g.n, 8)).astype(np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(forward(cfg, params, jnp.asarray(feats),
                             jnp.asarray(directed_edges(g.edges))))
    out = simulate_bsp_forward(cfg, params, plan, feats)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_bsp_forward_records_its_dispatch_and_is_named(small_siot):
    from repro.gnn.distributed import make_bsp_forward, scatter_features
    from repro.launch.mesh import make_mesh

    g = small_siot
    _, _, plan = _plan_for(g, 1)
    cfg = GNNConfig("gcn", (g.features.shape[1], 16, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1,), ("data",))
    fwd = make_bsp_forward(cfg, plan, mesh, aggregate="segment")
    blocks = jnp.asarray(scatter_features(plan, g.features))
    assert fwd.spans is None
    plain = np.asarray(fwd(params, blocks))
    fwd.spans = []
    traced = np.asarray(fwd(params, blocks))
    assert [n for n, _, _ in fwd.spans] == ["bsp.dispatch"]
    assert fwd.spans[0][1] <= fwd.spans[0][2]
    np.testing.assert_array_equal(plain, traced)
    assert "module @jit_bsp_forward " in fwd.lower(params, blocks).as_text()


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import synthetic_siot
    from repro.gnn import (GNNConfig, init_params, forward, directed_edges,
                           compile_plan, make_bsp_forward, scatter_features,
                           gather_outputs)
    from repro.core.partition import partition_from_assign

    g = synthetic_siot(n=120, target_links=300)
    assign = np.random.default_rng(0).integers(0, 4, size=g.n)
    part = partition_from_assign(g, assign, 4, {})
    plan = compile_plan(g, part)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ('data',))
    blocks = jnp.asarray(scatter_features(plan, g.features))
    sd = jnp.asarray(directed_edges(g.edges))
    for model in ['gcn', 'sage', 'gat']:
        cfg = GNNConfig(model, (52, 16, 2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        ref = np.asarray(forward(cfg, params, jnp.asarray(g.features), sd))
        for ex in ['ppermute', 'allgather']:
            with mesh:
                fwd = make_bsp_forward(cfg, plan, mesh, exchange=ex)
                out_blocks = np.asarray(jax.jit(fwd)(params, blocks))
            out = gather_outputs(plan, out_blocks, g.n)
            err = float(np.abs(ref - out).max() / (np.abs(ref).max() + 1e-9))
            assert err < 1e-4, (model, ex, err)
    print('MULTIDEV_OK')
""")


def test_shard_map_multidevice_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr
