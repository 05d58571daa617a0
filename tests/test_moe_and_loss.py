"""Units born from the §Perf hillclimb: grouped GEMM adjoints, block-capacity
MoE semantics, sharded CE equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.common import LMConfig, sharded_ce_loss
from repro.models.moe import grouped_gemm, moe_ffn, moe_ffn_dense_ref, router_topk

RNG = np.random.default_rng(0)


def _dense_grouped(x, w, gs):
    bounds = jnp.cumsum(gs)
    gid = jnp.searchsorted(bounds, jnp.arange(x.shape[0]), side="right")
    return jnp.einsum("mk,mkn->mn", x, w[gid])


@pytest.mark.parametrize("m,k,n,g", [(32, 16, 12, 4), (64, 8, 8, 8),
                                     (16, 32, 4, 2)])
def test_grouped_gemm_forward_and_adjoints(m, k, n, g):
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(g, k, n)).astype(np.float32))
    sizes = RNG.multinomial(m, np.ones(g) / g)
    gs = jnp.asarray(sizes, jnp.int32)
    np.testing.assert_allclose(np.asarray(grouped_gemm(x, w, gs)),
                               np.asarray(_dense_grouped(x, w, gs)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda x, w: (grouped_gemm(x, w, gs) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: (_dense_grouped(x, w, gs) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_router_topk_weights_normalized():
    x = jnp.asarray(RNG.normal(size=(3, 5, 16)).astype(np.float32))
    wr = jnp.asarray(RNG.normal(size=(16, 8)).astype(np.float32))
    idx, w, aux = router_topk(x, wr, 3)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0.0
    assert idx.shape == (3, 5, 3)
    assert int(idx.max()) < 8


def test_moe_capacity_drops_overflow():
    """With capacity_factor tiny, overflow rows are dropped, not corrupted."""
    cfg = LMConfig(name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                   n_kv_heads=2, d_ff=0, vocab=64, n_experts=4, top_k=2,
                   expert_d_ff=8, capacity_factor=0.25, dtype=jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {"router": jax.random.normal(k[0], (16, 4)) * 0.1,
         "w13": jax.random.normal(k[1], (4, 16, 16)) * 0.1,
         "w2": jax.random.normal(k[2], (4, 8, 16)) * 0.1}
    x = jax.random.normal(k[3], (2, 8, 16))
    mesh = make_mesh((1, 1), ("data", "model"))
    out, _ = jax.jit(lambda p, x: moe_ffn(cfg, p, x, mesh, ("data",)))(p, x)
    assert bool(jnp.isfinite(out).all())
    # Dropped tokens contribute zero, so |out| <= |dense ref|-ish magnitude.
    ref, _ = moe_ffn_dense_ref(cfg, p, x)
    assert float(jnp.abs(out).max()) <= float(jnp.abs(ref).max()) * 2 + 1e-3


def test_sharded_ce_equals_naive():
    B, L, V = 3, 7, 50
    logits = jnp.asarray(RNG.normal(size=(B, L, V)).astype(np.float32)) * 3
    labels = jnp.asarray(RNG.integers(0, V, size=(B, L)), jnp.int32)
    labels = labels.at[0, 0].set(-100)
    loss = sharded_ce_loss(logits, labels)
    # Naive reference
    mask = (labels >= 0)
    lab = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    ref = ((lse - gold) * mask).sum() / mask.sum()
    assert float(loss) == pytest.approx(float(ref), rel=1e-6)
    # Grads agree
    g1 = jax.grad(lambda l: sharded_ce_loss(l, labels))(logits)
    g2 = jax.grad(lambda l: (
        (jax.scipy.special.logsumexp(l, -1)
         - jnp.take_along_axis(l, lab[..., None], -1)[..., 0]) * mask
    ).sum() / mask.sum())(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


def test_sharded_ce_extreme_logits_stable():
    logits = jnp.asarray([[[1e4, -1e4, 0.0]]], jnp.float32)
    labels = jnp.asarray([[0]], jnp.int32)
    assert float(sharded_ce_loss(logits, labels)) == pytest.approx(0.0,
                                                                   abs=1e-3)
