"""Launch-layer units: HLO collective parser, roofline math, registry,
sharding-spec divisibility for every (arch x shape)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import (ARCHS, all_cells, applicable_shapes, get_config,
                           input_specs)
from repro.launch.hlo import (_shape_bytes, model_flops_for,
                              parse_collectives, roofline_terms, _wire_bytes)
from repro.models.common import SHAPES


def test_roofline_peaks_keyed_by_device_kind():
    t = roofline_terms(197e12, 819e9, 50e9, "TPU v5 lite")
    assert t == {"compute": 1.0, "memory": 1.0, "collective": 1.0}
    with pytest.raises(ValueError, match="device_kind"):
        roofline_terms(1.0, 1.0, 1.0, "cpu")


def test_shape_bytes():
    assert _shape_bytes("bf16[4,128]{1,0}") == 4 * 128 * 2
    assert _shape_bytes("f32[10]") == 40
    assert _shape_bytes("(bf16[2,2], f32[3])") == 8 + 12
    assert _shape_bytes("s32[]") == 0 or _shape_bytes("s32[]") == 4


def test_parse_collectives_literal_groups():
    hlo = """
  %ag = bf16[32,2048]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar.1 = f32[128]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %cp = f32[64]{0} collective-permute(%y), source_target_pairs={{0,1}}
"""
    cs = parse_collectives(hlo)
    assert len(cs) == 3
    ag, ar, cp = cs
    assert ag.kind == "all-gather" and ag.group_size == 4
    assert ag.bytes_buffer == 32 * 2048 * 2
    assert ar.kind == "all-reduce" and ar.group_size == 2
    assert cp.wire_bytes == 64 * 4


def test_parse_collectives_iota_groups():
    hlo = "%ag = bf16[16,16]{1,0} all-gather(%p), replica_groups=[32,16]<=[512], dimensions={0}"
    (c,) = parse_collectives(hlo)
    assert c.group_size == 16


def test_wire_bytes_model():
    assert _wire_bytes("all-reduce", 100, 2) == pytest.approx(100.0)
    assert _wire_bytes("all-gather", 160, 16) == pytest.approx(150.0)
    assert _wire_bytes("reduce-scatter", 10, 16) == pytest.approx(150.0)
    assert _wire_bytes("all-reduce", 100, 1) == 0.0


def test_model_flops_accounting():
    cfg = get_config("llama3.2-1b")
    tr = model_flops_for(cfg, SHAPES["train_4k"])
    n = cfg.params_count()
    assert tr == pytest.approx(6.0 * n * 4096 * 256)
    # MoE: active params only.
    k2 = get_config("kimi-k2-1t-a32b")
    tr2 = model_flops_for(k2, SHAPES["train_4k"])
    assert tr2 < 6.0 * k2.params_count() * 4096 * 256 * 0.1   # ~32B active


def test_registry_cells_and_skips():
    cells = list(all_cells())
    assert len(cells) == 40
    skipped = [c for c in cells if c[2]]
    assert len(skipped) == 8                    # long_500k skips
    assert all(s == "long_500k" for _, s, r in skipped if r)
    assert "long_500k" in applicable_shapes("zamba2-1.2b")
    assert "long_500k" in applicable_shapes("xlstm-1.3b")
    assert "long_500k" not in applicable_shapes("llama3.2-1b")


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_input_specs_are_abstract(arch):
    cfg = get_config(arch)
    for shape_name in applicable_shapes(arch):
        shape = SHAPES[shape_name]
        specs = input_specs(cfg, shape)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        assert specs["tokens"].shape[0] == shape.global_batch


def _mesh_div_check(spec: P, shape, mesh_shape):
    """Every sharded dim must divide by the product of its axes."""
    sizes = {"pod": 2, "data": 16, "model": 16}
    for dim, names in zip(shape, tuple(spec) + (None,) * len(shape)):
        if names is None:
            continue
        ns = names if isinstance(names, tuple) else (names,)
        prod = 1
        for nm in ns:
            prod *= sizes[nm]
        assert dim % prod == 0, (spec, shape)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_spec_divisibility(arch):
    """Every parameter's PartitionSpec divides its dims on the 2x16x16 mesh
    — the static precondition for the multi-pod dry-run."""
    from repro import models as zoo
    from repro.models.transformer import Dist

    cfg = get_config(arch)
    dist = Dist(None, batch_axes=("pod", "data"))
    params_abs = jax.eval_shape(
        lambda: zoo.init_params(cfg, jax.random.PRNGKey(0)))
    specs = zoo.param_specs(cfg, dist)
    flat_p = jax.tree_util.tree_leaves_with_path(params_abs)
    flat_s = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (pp, leaf), (sp, spec) in zip(flat_p, flat_s):
        assert jax.tree_util.keystr(pp) == jax.tree_util.keystr(sp)
        _mesh_div_check(spec, leaf.shape, (2, 16, 16))
