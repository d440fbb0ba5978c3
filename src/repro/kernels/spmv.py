"""Pallas TPU SpMV kernels — ``a(i) = B(i,j) · c(j)`` (paper §VI-A).

Two kernels matching the paper's two distributed algorithms:

- :func:`spmv_ell` — row-block leaf for the universe (row-based) strategy.
  Operates on the row-block ELL layout (layout.py): grid over
  (row-block, nnz-block); the segmented row reduction is a one-hot matmul on
  the MXU; the dense vector ``c`` is held in VMEM and gathered per block.

- :func:`spmv_coo_phase1` — two-phase segmented reduction for the non-zero
  (position-space) strategy: phase 1 (this kernel) computes, per nnz block,
  rank-compacted partial sums + the row id of each rank; phase 2 (a cheap
  XLA ``segment_sum`` in ops.py) merges block partials. This replaces the
  GPU leaf's atomic reductions — the TPU has no atomics, so block-local
  compaction + a small fixup is the idiomatic equivalent (DESIGN.md §2).

VMEM budget: with ``block_r=8``-row output tiles, ``block_n=128`` nnz lanes
and ``c`` resident, the working set is ``c`` (4·m bytes) + 3 nnz blocks +
the (8, 128) one-hot tile — well under the ~16 MiB/core VMEM for m ≤ 1M.
For larger m the column dimension must be blocked with column-bucketed
layouts; see DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import formats as fmt


def supports(format: "fmt.Format", space: str) -> bool:
    """Format-dispatch query (core.lower consults this before emitting).

    Row (universe) leaves consume any format whose dimension-0 partition
    maps to contiguous storage — CSR directly, DCSR/COO via the densified
    row-window view. Non-zero leaves need an nnz-splittable position space
    (any unblocked sparse format; non-row-major roots like CSC reduce over
    the full output extent instead of a row window). Blocked formats
    (BCSR) route to the direct blocked leaves (kernels/bcsr.py) under both
    strategies — block-row windows / stored-block splits."""
    return fmt.supports_2d_default(format, space)


# ---------------------------------------------------------------------------
# Row-based (universe) kernel
# ---------------------------------------------------------------------------

def _spmv_ell_kernel(rows_ref, crd_ref, vals_ref, c_ref, out_ref, *,
                     block_r: int):
    """One (row-block, nnz-block) grid step."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[0, :]                      # (block_n,) relative row ids
    crd = crd_ref[0, :]                        # (block_n,) columns
    vals = vals_ref[0, :]                      # (block_n,)
    cvals = jnp.take(c_ref[:], crd, axis=0)    # VMEM gather
    prod = vals * cvals                        # (block_n,)
    # segmented reduce as a one-hot MXU matvec; padding rows_rel == block_r
    # select no output row.
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_r, rows.shape[0]), 0)
    onehot = (iota_r == rows[None, :]).astype(prod.dtype)
    out_ref[0, :] += onehot @ prod


def spmv_ell(rows_rel: jax.Array, crd: jax.Array, vals: jax.Array,
             c: jax.Array, *, block_r: int = 8, block_n: int = 128,
             interpret: bool) -> jax.Array:
    """Returns y of shape (n_rblocks * block_r,).

    Inputs are the `layout.ell_pack` arrays: (n_rblocks, bnnz) each; ``c``
    is the full dense vector (replicated operand of the row strategy).
    """
    n_rblocks, bnnz = rows_rel.shape
    assert bnnz % block_n == 0
    grid = (n_rblocks, bnnz // block_n)
    out = pl.pallas_call(
        functools.partial(_spmv_ell_kernel, block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),   # rows_rel
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),   # crd
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),   # vals
            pl.BlockSpec(c.shape, lambda i, n: (0,)),          # c in VMEM
        ],
        out_specs=pl.BlockSpec((1, block_r), lambda i, n: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rblocks, block_r), vals.dtype),
        interpret=interpret,
    )(rows_rel, crd, vals, c)
    return out.reshape(n_rblocks * block_r)


# ---------------------------------------------------------------------------
# Non-zero (position-space) kernel — two-phase segmented reduction
# ---------------------------------------------------------------------------

def _coo_phase1_kernel(rows_ref, crd_ref, vals_ref, c_ref, psum_ref, prow_ref):
    rows = rows_ref[0, :]
    crd = crd_ref[0, :]
    vals = vals_ref[0, :]
    prod = vals * jnp.take(c_ref[:], crd, axis=0)
    # rank-compact: rows are sorted within the block; rank = #row-changes
    first = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == 0
    prev = jnp.roll(rows, 1)
    newseg = jnp.where(first, True, rows != prev)
    rank = jnp.cumsum(newseg.astype(jnp.int32)) - 1          # (block_n,)
    bn = rows.shape[0]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    onehot = (iota_r == rank[None, :]).astype(prod.dtype)
    psum_ref[0, :] = onehot @ prod                            # per-rank sums
    # row id per rank: only the segment-start position contributes (others
    # multiply by newseg == 0). Ranks past the block's last rank select
    # nothing -> row 0 with a zero partial, dropped/harmless in phase 2.
    # f32 matmul keeps row ids exact up to 2^24 (fine for shard-local rows;
    # larger shards would split the id into hi/lo lanes).
    prow_ref[0, :] = (onehot @ (rows * newseg).astype(prod.dtype)
                      ).astype(jnp.int32)


def spmv_coo_phase1(rows: jax.Array, crd: jax.Array, vals: jax.Array,
                    c: jax.Array, *, block_n: int = 128,
                    interpret: bool):
    """Phase 1: per-block rank partial sums + rank row ids.

    ``rows`` must be sorted (COO order — true after a non-zero partition of
    a row-major sparse tensor). Returns (partials, partial_rows), each of
    shape (n_blocks, block_n); ops.spmv_nnz merges with a segment-sum.
    """
    nnz = rows.shape[0]
    assert nnz % block_n == 0
    nb = nnz // block_n
    r2 = rows.reshape(nb, block_n)
    c2 = crd.reshape(nb, block_n)
    v2 = vals.reshape(nb, block_n)
    psum, prow = pl.pallas_call(
        _coo_phase1_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec(c.shape, lambda b: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block_n), vals.dtype),
            jax.ShapeDtypeStruct((nb, block_n), jnp.int32),
        ],
        interpret=interpret,
    )(r2, c2, v2, c)
    return psum, prow
