"""Pallas TPU SpMM kernel — ``A(i,j) = B(i,k) · C(k,j)`` (paper §VI-A).

Row-block ELL leaf for the row-based distributed algorithm. Grid is
(row-block, j-block, nnz-block); each step gathers the needed rows of the
dense operand ``C`` into VMEM, scales by the sparse values, and reduces into
the (block_r, block_j) output tile with a one-hot MXU matmul:

    A_tile += onehot(rows_rel)[block_r, block_n] @ (vals ⊙ C[crd, j_tile])

This is the Senanayake et al. SpMM schedule re-tiled for the MXU: the
``block_n``-long gather feeds a (block_r × block_n) × (block_n × block_j)
matmul, so MXU utilization scales with nnz density rather than row lengths.
C is blocked along j only; its k extent stays resident in VMEM (fits for
k ≤ ~32K at block_j=128; larger k requires k-blocking with crd bucketing,
see DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import formats as fmt


def supports(format: "fmt.Format", space: str) -> bool:
    """Format-dispatch query — same capability contract as spmv (the sparse
    operand's row/nnz iteration is identical; only the dense operand
    changes). BCSR lowers directly: each stored block is a dense
    (br, bc) @ (bc, J) MXU matmul (kernels/bcsr.py)."""
    return fmt.supports_2d_default(format, space)


def _spmm_ell_kernel(rows_ref, crd_ref, vals_ref, c_ref, out_ref, *,
                     block_r: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[0, :]                        # (block_n,)
    crd = crd_ref[0, :]
    vals = vals_ref[0, :]
    cg = jnp.take(c_ref[...], crd, axis=0)       # (block_n, block_j) gather
    prod = vals[:, None] * cg
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_r, rows.shape[0]), 0)
    onehot = (iota_r == rows[None, :]).astype(prod.dtype)
    out_ref[0, :, :] += onehot @ prod            # MXU


def spmm_ell(rows_rel: jax.Array, crd: jax.Array, vals: jax.Array,
             C: jax.Array, *, block_r: int = 8, block_n: int = 128,
             block_j: int = 128, interpret: bool) -> jax.Array:
    """Returns Y of shape (n_rblocks * block_r, J_padded).

    ELL arrays: (n_rblocks, bnnz); C: (K, J). J is padded to block_j.
    """
    n_rblocks, bnnz = rows_rel.shape
    K, J = C.shape
    assert bnnz % block_n == 0
    jpad = -(-J // block_j) * block_j
    if jpad != J:
        C = jnp.pad(C, ((0, 0), (0, jpad - J)))
    grid = (n_rblocks, jpad // block_j, bnnz // block_n)
    out = pl.pallas_call(
        functools.partial(_spmm_ell_kernel, block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j, n: (i, n)),
            pl.BlockSpec((1, block_n), lambda i, j, n: (i, n)),
            pl.BlockSpec((1, block_n), lambda i, j, n: (i, n)),
            pl.BlockSpec((K, block_j), lambda i, j, n: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_r, block_j), lambda i, j, n: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n_rblocks, block_r, jpad), vals.dtype),
        interpret=interpret,
    )(rows_rel, crd, vals, C)
    return out.reshape(n_rblocks * block_r, jpad)[:, :J]
