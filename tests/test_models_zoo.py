"""Per-arch smoke tests (deliverable f): REDUCED configs of every assigned
architecture run one forward + one train step on CPU; output shapes + no
NaNs.  Decode==forward consistency per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as zoo
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models.transformer import Dist, vocab_padded
from repro.train import OptConfig, init_opt_state, make_train_step


def _smoke(arch):
    return dataclasses.replace(get_smoke_config(arch), dtype=jnp.float32)


def _batch(cfg, B=2, L=16, seed=0):
    kq, kl = jax.random.split(jax.random.PRNGKey(seed))
    b = {"tokens": jax.random.randint(kq, (B, L), 0, cfg.vocab),
         "labels": jax.random.randint(kl, (B, L), 0, cfg.vocab)}
    if cfg.family == "encdec":
        b["frames"] = jnp.ones((B, cfg.frontend_len, cfg.frontend_dim),
                               jnp.float32)
    if cfg.family == "vlm":
        b["patches"] = jnp.ones((B, cfg.frontend_len, cfg.frontend_dim),
                                jnp.float32)
    return b


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_shapes_no_nan(arch):
    cfg = _smoke(arch)
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = zoo.forward(cfg, params, batch)
    L_expect = 16 + (cfg.frontend_len if cfg.family == "vlm" else 0)
    assert logits.shape == (2, L_expect, vocab_padded(cfg))
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_train_step(arch):
    cfg = _smoke(arch)
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = OptConfig(name=cfg.optimizer, lr=1e-2)
    ostate = init_opt_state(opt_cfg, params)
    step = jax.jit(make_train_step(cfg, Dist(), opt_cfg))
    batch = _batch(cfg)
    l0 = None
    for s in range(3):
        params, ostate, _, m = step(params, ostate, None, batch)
        assert np.isfinite(float(m["loss"])), arch
        l0 = float(m["loss"]) if l0 is None else l0
    assert float(m["loss"]) < l0, f"{arch}: loss did not decrease"


@pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-moe-16b",
                                  "zamba2-1.2b", "xlstm-1.3b",
                                  "seamless-m4t-medium", "internvl2-2b",
                                  "qwen2.5-32b"])
def test_prefill_decode_matches_forward(arch):
    """prefill(prompt) + decode_step(next) == forward(prompt+next)[-1]."""
    cfg = _smoke(arch)
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    pb = {"tokens": toks[:, :8]}
    fb = {"tokens": toks[:, :9]}
    if cfg.family == "encdec":
        frames = jnp.ones((2, cfg.frontend_len, cfg.frontend_dim), jnp.float32)
        pb["frames"] = frames
        fb["frames"] = frames
    if cfg.family == "vlm":
        patches = jnp.ones((2, cfg.frontend_len, cfg.frontend_dim),
                           jnp.float32)
        pb["patches"] = patches
        fb["patches"] = patches
    # max_len must cover prompt (+ patch positions for vlm) + new tokens.
    max_len = 16 + (cfg.frontend_len if cfg.family == "vlm" else 0)
    lg_pf, cache = zoo.prefill(cfg, params, pb, max_len=max_len)
    lg_dec, cache = zoo.decode_step(cfg, params, toks[:, 8:9], cache)
    full, _ = zoo.forward(cfg, params, fb)
    np.testing.assert_allclose(np.asarray(lg_dec[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyper-parameters."""
    spec = {
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    }
    for arch, (L, d, H, kv, ff, V) in spec.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == H, arch
        assert cfg.n_kv_heads == kv, arch
        assert cfg.vocab == V, arch
        if cfg.n_experts:
            assert cfg.expert_d_ff == ff, arch
        else:
            assert cfg.d_ff == ff, arch
    # MoE structure
    ds = get_config("deepseek-moe-16b")
    assert (ds.n_experts, ds.top_k, ds.n_shared_experts) == (64, 6, 2)
    k2 = get_config("kimi-k2-1t-a32b")
    assert (k2.n_experts, k2.top_k) == (384, 8)
    # Param-count sanity vs the model names.
    assert 0.9e9 < get_config("llama3.2-1b").params_count() < 1.6e9
    assert 30e9 < get_config("qwen2.5-32b").params_count() < 36e9
    assert 0.9e12 < k2.params_count() < 1.15e12


def test_moe_sharded_equals_dense_ref_subprocess_free():
    """moe_ffn (1x1 mesh) == moe_ffn_dense_ref on the same inputs."""
    from repro.models.moe import moe_ffn, moe_ffn_dense_ref
    from repro.models.common import LMConfig
    cfg = LMConfig(name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                   n_kv_heads=2, d_ff=0, vocab=64, n_experts=4, top_k=2,
                   expert_d_ff=8, capacity_factor=4.0, dtype=jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {"router": jax.random.normal(k[0], (16, 4)) * 0.1,
         "w13": jax.random.normal(k[1], (4, 16, 16)) * 0.1,
         "w2": jax.random.normal(k[2], (4, 8, 16)) * 0.1}
    x = jax.random.normal(k[3], (2, 6, 16))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    ref, _ = moe_ffn_dense_ref(cfg, p, x)
    out, _ = jax.jit(lambda p, x: moe_ffn(cfg, p, x, mesh, ("data",)))(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
