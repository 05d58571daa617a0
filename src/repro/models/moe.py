"""Fine-grained MoE layer (DeepSeekMoE / Kimi-K2 style) — expert parallel.

Parallelism (DESIGN.md §5):
  * experts sharded over the 'model' axis (E_local = E / model_size),
  * expert weights additionally ZeRO-3 sharded on d_model over 'data',
    all-gathered per layer inside the manual region (2 TB of Kimi experts
    never exist unsharded anywhere),
  * tokens are batch-sharded and REPLICATED over 'model', so dispatch is a
    local mask + sort — the combine is one psum over 'model', the exact same
    collective a dense TP MLP pays.  No all-to-all: this is the paper's
    C_T insight applied to experts (co-locate computation with data already
    in place rather than moving tokens).

Capacity: each model shard processes at most CAP = T*k/model_size * cf
assignments (static shape); overflow tokens drop their weakest expert —
standard capacity-factor semantics.

The router, shared experts, and the top-k run OUTSIDE the manual region in
plain GSPMD land.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import LMConfig


def router_topk(x, w_router, k: int):
    """x (..., d) -> (idx (..., k) i32, weights (..., k) fp32, aux_loss)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e.
    E = w_router.shape[-1]
    flat = probs.reshape(-1, E)
    me = flat.mean(0)
    one_hot = jax.nn.one_hot(idx.reshape(-1, k), E, dtype=jnp.float32).sum(1)
    ce = one_hot.mean(0) / k
    aux = E * jnp.sum(me * ce)
    return idx, w.astype(x.dtype), aux


@jax.custom_vjp
def grouped_gemm(x, w, gs):
    """Grouped GEMM y[i] = x[i] @ w[group(i)] with hand-written VJP.

    jax.lax.ragged_dot's autodiff computes dW densely (every row against
    every group: x E_local more FLOPs — measured 30x total-step compute on
    kimi train_4k).  The proper adjoints are themselves ragged:
      dx = ragged_dot(dy, w^T, gs)                      (mode 1)
      dW = ragged_dot_general(x, dy, ragged-contracting) (mode 2: grouped
           outer product, same FLOPs as the forward)
    """
    return jax.lax.ragged_dot(x, w, gs)


def _gg_fwd(x, w, gs):
    return jax.lax.ragged_dot(x, w, gs), (x, w, gs)


def _gg_bwd(res, dy):
    x, w, gs = res
    dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), gs)
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[],
    )
    dw = jax.lax.ragged_dot_general(
        x, dy, gs, dn, preferred_element_type=w.dtype)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_gemm.defvjp(_gg_fwd, _gg_bwd)


def moe_ffn(
    cfg: LMConfig,
    p: dict,
    x: jnp.ndarray,
    mesh,
    batch_axes,
    model_axis: str = "model",
    data_axis: str = "data",
    fsdp_axes=None,
):
    """x (B, L, d) -> (B, L, d) MoE output (routed experts only; shared
    experts and router aux handled by the caller).

    p: {'w13': (E, d, 2*f), 'w2': (E, f, d)} sharded
       P(model_axis, fsdp, None) / P(model_axis, None, fsdp).
    ``idx``/``weights`` come from router_topk on the same x.
    """
    fsdp_axes = tuple(fsdp_axes) if fsdp_axes else (data_axis,)
    idx, weights, aux = router_topk(x, p["router"], cfg.top_k)

    B, L, d = x.shape
    k = cfg.top_k
    msize = mesh.shape[model_axis]
    E_local = cfg.n_experts // msize
    # Per-device token count (batch is sharded over batch_axes).
    bshard = 1
    for a in batch_axes:
        bshard *= mesh.shape[a]
    T_local = (B // bshard) * L

    # Per-expert capacity (standard MoE semantics): overflow beyond C drops.
    C = int((T_local * k / cfg.n_experts) * cfg.capacity_factor)
    C = max(64, ((C + 63) // 64) * 64)

    def body(xb, idxb, wb, w13, w2):
        # xb (B_l, L, d); idxb/wb (B_l, L, k); w13 (E_local, d/dsize, 2f).
        m_idx = jax.lax.axis_index(model_axis)
        w13 = jax.lax.all_gather(w13, fsdp_axes, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=2, tiled=True)
        w13 = w13.astype(xb.dtype)
        w2 = w2.astype(xb.dtype)

        xf = xb.reshape(-1, d)
        T = xf.shape[0]
        flat_idx = idxb.reshape(T * k)
        flat_w = wb.reshape(T * k)
        local_e = flat_idx - m_idx * E_local
        is_mine = (local_e >= 0) & (local_e < E_local)
        # Sort assignments by local expert (non-mine to the tail), then give
        # each expert a FIXED block of C rows — the compute becomes a plain
        # batched GEMM (einsum), which is FLOP-exact on every backend
        # (ragged_dot decomposes densely off-TPU: measured 24x FLOPs).
        sort_key = jnp.where(is_mine, local_e, E_local)
        order = jnp.argsort(sort_key, stable=True)
        gs = jnp.bincount(jnp.where(is_mine, local_e, E_local),
                          length=E_local + 1)[:E_local]
        offs = jnp.concatenate([jnp.zeros((1,), gs.dtype),
                                jnp.cumsum(gs)[:-1]])
        pos = offs[:, None] + jnp.arange(C)[None, :]        # (E_local, C)
        valid = jnp.arange(C)[None, :] < jnp.minimum(gs, C)[:, None]
        src = order[jnp.minimum(pos, T * k - 1)]            # rows in flat
        tok = src // k                                      # (E_local, C)
        xB = xf[tok] * valid[..., None].astype(xf.dtype)    # (E_local, C, d)
        h = jnp.einsum("ecd,edf->ecf", xB, w13)
        g, u = jnp.split(h, 2, axis=-1)
        act = (jax.nn.silu(g.astype(jnp.float32)) *
               u.astype(jnp.float32)).astype(xB.dtype)
        y = jnp.einsum("ecf,efd->ecd", act, w2)             # (E_local, C, d)
        y = y * flat_w[src][..., None] * valid[..., None].astype(y.dtype)
        out = jnp.zeros((T, d), y.dtype).at[tok.reshape(-1)].add(
            y.reshape(-1, d))
        out = jax.lax.psum(out, model_axis)
        return out.reshape(xb.shape)

    fs = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    bspec = P(batch_axes, None, None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(batch_axes, None, None), P(batch_axes, None, None),
                  P(model_axis, fs, None),
                  P(model_axis, None, fs)),
        out_specs=bspec,
        check_vma=False,
    )(x, idx, weights, p["w13"], p["w2"])
    return out, aux


def moe_ffn_dense_ref(cfg: LMConfig, p: dict, x: jnp.ndarray):
    """Oracle: every expert on every token, one-hot combine (tests only)."""
    idx, weights, aux = router_topk(x, p["router"], cfg.top_k)
    B, L, d = x.shape
    xf = x.reshape(-1, d)
    h = jnp.einsum("td,edf->tef", xf, p["w13"].astype(x.dtype))
    g, u = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    y = jnp.einsum("tef,efd->ted", act.astype(xf.dtype),
                   p["w2"].astype(x.dtype))
    comb = jnp.zeros((xf.shape[0], cfg.n_experts), x.dtype)
    flat_idx = idx.reshape(-1, cfg.top_k)
    flat_w = weights.reshape(-1, cfg.top_k)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], flat_idx].add(flat_w)
    out = jnp.einsum("te,ted->td", comb, y)
    return out.reshape(B, L, d), aux
