"""Block-sparse SpMM Pallas kernel — the GNN aggregation hot spot on TPU.

Hardware adaptation (DESIGN.md §3): the paper's edge servers run scalar CSR
gather loops on CPUs; a mechanical port would be a data-dependent gather,
which the TPU's systolic design punishes.  Instead we re-tile aggregation as
**block-sparse matmul**: the (GLAD-ordered) adjacency is chopped into dense
(bm, bk) link blocks; only nonempty blocks are stored, and each one becomes
an MXU matmul-accumulate against a (bk, d) feature tile.  GLAD's layout (and
degree ordering within a partition) concentrates links near the diagonal, so
block density — and thus MXU utilization — is a direct function of layout
quality: the paper's C_T minimization doubles as an MXU-efficiency knob.

Layout:
  values     (n_dst_blocks * max_blocks, bm, bk)  dense link-weight blocks
  block_cols (n_dst_blocks, max_blocks) int32     source block-row per block
                                                  (0-padded; padded values=0)
  feats      (n_src_blocks * bk, d)
  out        (n_dst_blocks * bm, d)

Grid: (n_dst_blocks, max_blocks, d_blocks).  ``block_cols`` rides in scalar
prefetch so the feature BlockSpec index_map can pick the right (bk, d) tile —
the canonical TPU scalar-prefetch block-sparse pattern.  The accumulator
lives in the output VMEM block across the j loop (dimension_semantics mark j
"arbitrary" so the block persists).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(block_cols_ref, vals_ref, feats_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.dot(
        vals_ref[0].astype(jnp.float32),
        feats_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] += acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bd", "interpret"))
def spmm(values, block_cols, feats, *, bm: int, bk: int, bd: int = 128,
         interpret: bool = False):
    """Block-sparse A @ H.  See module docstring for the layout contract."""
    n_dst_blocks, max_blocks = block_cols.shape
    n_rows_out = n_dst_blocks * bm
    d = feats.shape[1]
    bd = min(bd, d)
    assert d % bd == 0, (d, bd)
    assert feats.shape[0] % bk == 0

    grid = (n_dst_blocks, max_blocks, d // bd)

    def vals_map(i, j, kd, cols):
        return (i * max_blocks + j, 0, 0)

    def feats_map(i, j, kd, cols):
        return (cols[i, j], kd)

    def out_map(i, j, kd, cols):
        return (i, kd)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), vals_map),
                pl.BlockSpec((bk, bd), feats_map),
            ],
            out_specs=pl.BlockSpec((bm, bd), out_map),
        ),
        # Inside shard_map (check_vma=True) the output varies over the same
        # mesh axes as the per-device feature table; outside it is empty.
        out_shape=jax.ShapeDtypeStruct((n_rows_out, d), feats.dtype,
                                       vma=jax.typeof(feats).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "parallel"),
        ),
        interpret=interpret,
    )(block_cols, values, feats)
    return out


def spmm_jnp(values, block_cols, feats, bm: int, bk: int):
    """Vectorized jnp execution of the kernel's exact BSR layout.

    The non-TPU fallback for ``spmm``: one gather of (bk, d) feature tiles by
    ``block_cols`` plus one einsum contraction, instead of interpret-mode
    Pallas (O(python) per block) or the per-block ``ref.spmm_ref`` loop.  It
    may differ from the kernel only in accumulation order (the einsum
    contracts all ``max_blocks`` tiles at once vs the kernel's sequential
    j-loop); both accumulate in fp32.
    """
    n_dst_blocks, max_blocks = block_cols.shape
    d = feats.shape[1]
    assert feats.shape[0] % bk == 0, (feats.shape, bk)
    tiles = feats.reshape(-1, bk, d)
    gathered = tiles[block_cols]                   # (nb, maxb, bk, d)
    vals = values.reshape(n_dst_blocks, max_blocks, bm, bk)
    out = jnp.einsum(
        "nmbk,nmkd->nbd",
        vals.astype(jnp.float32), gathered.astype(jnp.float32))
    return out.reshape(n_dst_blocks * bm, d).astype(feats.dtype)


# --------------------------------------------------------------- host packing
def build_bsr(
    src_dst: np.ndarray,
    weights: np.ndarray | None,
    n: int,
    bm: int = 8,
    bk: int = 128,
):
    """Pack a directed edge list into the kernel's BSR layout.

    Returns (values, block_cols, n_pad) where n_pad = rows padded to
    lcm-friendly multiples of bm (dst) and bk (src).  Padded blocks carry
    zero weights and column 0 — they multiply the first feature tile by zero,
    keeping the grid rectangular with no masking logic in the kernel.
    """
    if weights is None:
        weights = np.ones(len(src_dst), dtype=np.float32)
    n_dst_pad = max(bm, ((n + bm - 1) // bm) * bm)
    n_src_pad = max(bk, ((n + bk - 1) // bk) * bk)
    n_dst_blocks = n_dst_pad // bm

    by_block: dict[tuple[int, int], np.ndarray] = {}
    if len(src_dst):
        ib = src_dst[:, 1] // bm           # dst block
        jb = src_dst[:, 0] // bk           # src block
        order = np.lexsort((jb, ib))
        s = src_dst[order]
        w = weights[order]
        ib, jb = ib[order], jb[order]
        bounds = np.flatnonzero(np.diff(ib * (n_src_pad // bk + 1) + jb)) + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [len(s)]])
        for a, b in zip(starts, ends):
            key = (int(ib[a]), int(jb[a]))
            blk = np.zeros((bm, bk), np.float32)
            rows = s[a:b, 1] - key[0] * bm
            cols = s[a:b, 0] - key[1] * bk
            np.add.at(blk, (rows, cols), w[a:b])
            by_block[key] = blk

    per_row: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(n_dst_blocks)]
    for (i, j), blk in by_block.items():
        per_row[i].append((j, blk))
    max_blocks = max(1, max((len(r) for r in per_row), default=1))

    values = np.zeros((n_dst_blocks * max_blocks, bm, bk), np.float32)
    block_cols = np.zeros((n_dst_blocks, max_blocks), np.int32)
    for i, row in enumerate(per_row):
        for k, (j, blk) in enumerate(sorted(row)):
            values[i * max_blocks + k] = blk
            block_cols[i, k] = j
    return values, block_cols, n_dst_pad, n_src_pad


def bsr_density(block_cols: np.ndarray, values: np.ndarray) -> float:
    """Fraction of nonzero entries within stored blocks (MXU efficiency)."""
    stored = values.size
    nnz = int((values != 0).sum())
    return nnz / max(stored, 1)
