"""Compiles for a described TPU v5e (v5e:2x2): the main path's kernel and
the sharded BSP forward go through the chip's own compiler without a chip.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and under several test
workers only the worker that runs this file may do so.  Keep these tests in
this one file for the same reason.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.gnn_paper import SIOT_GCN, SIOT_SAGE
from repro.core.partition import partition_from_assign
from repro.gnn import distributed
from repro.gnn.distributed import compile_plan, make_bsp_forward
from repro.gnn.models import init_params
from repro.graphs.datagraph import synthetic_siot
from repro.kernels.gnn_aggregate import spmm
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep such entries out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("d", [16, 52, 100])
def test_spmm_compiles_at_paper_widths(d, one_chip):
    """The BSR kernel at the paper's lane widths (hidden 16, SIoT 52,
    Yelp 100) with the default (bm, bk) = (8, 128) tiles."""
    n_dst_blocks, max_blocks, n_src = 64, 12, 1024
    values = jax.ShapeDtypeStruct((n_dst_blocks * max_blocks, 8, 128),
                                  jnp.float32, sharding=one_chip)
    cols = jax.ShapeDtypeStruct((n_dst_blocks, max_blocks), jnp.int32,
                                sharding=one_chip)
    feats = jax.ShapeDtypeStruct((n_src, d), jnp.float32, sharding=one_chip)
    compiled = spmm.lower(values, cols, feats, bm=8, bk=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cfg,exchange", [(SIOT_GCN, "ppermute"),
                                          (SIOT_SAGE, "allgather")],
                         ids=["gcn-ppermute", "sage-allgather"])
def test_bsp_forward_pallas_compiles_on_4_chip_mesh(cfg, exchange, topo,
                                                    monkeypatch):
    """The sharded BSP forward with the Pallas BSR aggregation, as it runs
    on four chips: the kernel is legal inside ``shard_map`` with its
    varying-axes check on, and the halo exchange is a real collective."""
    monkeypatch.setattr(distributed, "_on_tpu", lambda: True)
    g = synthetic_siot(n=600, target_links=2400, seed=3)
    assign = np.random.default_rng(0).integers(0, 4, size=g.n)
    plan = compile_plan(g, partition_from_assign(g, assign, 4, {}),
                        slack=0.5)
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    fwd = make_bsp_forward(cfg, plan, mesh, exchange=exchange,
                           aggregate="pallas")
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        init_params(jax.random.PRNGKey(0), cfg))
    blocks = jax.ShapeDtypeStruct(
        (plan.num_parts, plan.cap, cfg.layer_dims[0]), jnp.float32,
        sharding=NamedSharding(mesh, P("data")))
    text = fwd.lower(params, blocks).compile().as_text()
    assert "tpu_custom_call" in text
    collective = ("collective-permute" if exchange == "ppermute"
                  else "all-gather")
    assert collective in text
