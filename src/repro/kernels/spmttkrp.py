"""Pallas TPU SpMTTKRP kernel — ``A(i,l) = B(i,j,k) · C(j,l) · D(k,l)``.

Row-block ELL leaf over the CSF tensor's *flattened nnz* with per-nnz
(j, k) coordinates (packed by layout.ell_pack with ``extra``). Per grid
step:

    contrib[block_n, L] = vals ⊙ C[j, :] ⊙ D[k, :]
    A_tile[block_r, L]  += onehot(rows_rel) @ contrib          (MXU)

The factor matrices C, D stay VMEM-resident (J·L, K·L ≤ VMEM for the
factorization ranks the paper evaluates, L ≤ 64). The same kernel serves
both the row-based and the non-zero based distributed algorithms — only the
partitioning (and hence rows_rel construction) differs, which is exactly
the paper's separation of concerns.

SpTTV (``A(i,j) = B(i,j,k)·c(k)``) reuses spmv.spmv_ell with the level-1
position space as rows — no separate kernel needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import formats as fmt


def supports(format: "fmt.Format", space: str) -> bool:
    """Format-dispatch query for 3-D MTTKRP (and TTV). Universe needs a
    row-partitionable root plus a walkable body: a grouped (non-singleton
    compressed) middle level feeds the two-level pos/crd leaf (CSF
    directly, DCSF via the densified row window), and trailing-singleton
    trees (COO3) feed the FLAT per-position leaf bucketed by row window —
    the trailing-singleton walk of core/levels.py, so no conversion is
    needed. The nnz leaf consumes flat per-nnz (i, j, k) coordinates,
    which every unblocked 3-D sparse format provides."""
    caps = fmt.capabilities(format)
    if caps.order != 3:
        return False
    if space == "universe":
        grouped = (format.levels[1].compressed
                   and not format.levels[1].singleton)
        trailing = all(l.singleton for l in format.levels[1:])
        return caps.row_partitionable and (grouped or trailing)
    return caps.nnz_partitionable


def _spmttkrp_kernel(rows_ref, j_ref, k_ref, vals_ref, c_ref, d_ref, out_ref,
                     *, block_r: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[0, :]
    jj = j_ref[0, :]
    kk = k_ref[0, :]
    vals = vals_ref[0, :]
    cg = jnp.take(c_ref[...], jj, axis=0)       # (block_n, L)
    dg = jnp.take(d_ref[...], kk, axis=0)       # (block_n, L)
    contrib = vals[:, None] * cg * dg
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_r, rows.shape[0]), 0)
    onehot = (iota_r == rows[None, :]).astype(contrib.dtype)
    out_ref[0, :, :] += onehot @ contrib


def spmttkrp_ell(rows_rel: jax.Array, j: jax.Array, k: jax.Array,
                 vals: jax.Array, C: jax.Array, D: jax.Array, *,
                 block_r: int = 8, block_n: int = 128,
                 interpret: bool) -> jax.Array:
    """Returns A of shape (n_rblocks * block_r, L)."""
    n_rblocks, bnnz = rows_rel.shape
    L = C.shape[1]
    assert bnnz % block_n == 0
    grid = (n_rblocks, bnnz // block_n)
    out = pl.pallas_call(
        functools.partial(_spmttkrp_kernel, block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),
            pl.BlockSpec((1, block_n), lambda i, n: (i, n)),
            pl.BlockSpec(C.shape, lambda i, n: (0, 0)),
            pl.BlockSpec(D.shape, lambda i, n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, L), lambda i, n: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rblocks, block_r, L), vals.dtype),
        interpret=interpret,
    )(rows_rel, j, k, vals, C, D)
    return out.reshape(n_rblocks * block_r, L)
