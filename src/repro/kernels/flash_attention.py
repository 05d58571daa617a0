"""Flash attention Pallas kernel (blockwise online softmax), GQA-aware.

TPU tiling: the grid walks (batch, q_head, q_block, kv_block) with the
kv_block axis innermost ("arbitrary" semantics) so the running max / sum /
accumulator scratch persists in VMEM across the kv sweep.  Blocks strictly
above the causal diagonal are skipped via pl.when — for long-context decode
(Lq=1) only the prefix up to kv_len is visited numerically.

GQA: kv tiles are indexed by q_head // group_size, so a kv head's tile is
reused by its whole query group without materializing repeats (this is the
memory-term win over the naive repeat-then-attend reference).

Shapes: q (B, Hq, Lq, D); k/v (B, Hkv, Lk, D); kv_len (B,) i32 optional live
length per batch row (padded caches).  D rides whole in each block (<= 256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, causal, bq, bkv, lq, lk):
    b = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: q global row = i*bq + r + (lk - lq); kv col = j*bkv + c.
    q_off = lk - lq
    first_q = i * bq + q_off
    live = kv_len_ref[b]

    def body():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bkv, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, bkv)
        q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = k_pos < live
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev[:, 0], s.max(axis=1))[:, None]
        p = jnp.exp(s - m_new)                                 # (bq, bkv)
        corr = jnp.exp(m_prev - m_new)                         # (bq, 1)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)[:, None]
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    if causal:
        # Skip blocks entirely above the diagonal (and past live length).
        pl.when(jnp.logical_and(j * bkv <= first_q + bq - 1, j * bkv < live))(body)
    else:
        pl.when(j * bkv < live)(body)

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "bq", "bkv", "interpret"))
def flash_attention(
    q, k, v, kv_len=None, *, causal: bool = True, scale: float | None = None,
    bq: int = 128, bkv: int = 128, interpret: bool = False,
):
    """Blockwise attention.  Pads Lq/Lk internally; returns (B, Hq, Lq, D)."""
    B, Hq, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    bq = min(bq, max(8, 1 << (Lq - 1).bit_length()))
    bkv = min(bkv, max(8, 1 << (Lk - 1).bit_length()))
    lq_pad = ((Lq + bq - 1) // bq) * bq
    lk_pad = ((Lk + bkv - 1) // bkv) * bkv
    if lq_pad != Lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad - Lq), (0, 0)))
    if lk_pad != Lk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad - Lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad - Lk), (0, 0)))
    if kv_len is None:
        kv_len = jnp.full((B,), Lk, jnp.int32)
    kv_len = kv_len.astype(jnp.int32)

    grid = (B, Hq, lq_pad // bq, lk_pad // bkv)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bkv=bkv,
        lq=Lq, lk=Lk)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, kvl: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bkv, D),
                             lambda b, h, i, j, kvl: (b, h // group, j, 0)),
                pl.BlockSpec((1, 1, bkv, D),
                             lambda b, h, i, j, kvl: (b, h // group, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, D), lambda b, h, i, j, kvl: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, lq_pad, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len, q, k, v)
    return out[:, :, :Lq]
