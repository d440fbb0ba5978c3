"""Pallas TPU SpAdd3 kernel — ``A(i,j) = B(i,j) + C(i,j) + D(i,j)``.

The paper's headline fusion win (§VI-A: 11.8×/38.5× over PETSc/Trilinos,
which must run two pairwise adds with intermediate assembly). The fused
TPU leaf accumulates all three operands' row blocks into one dense
(block_r, block_m) VMEM tile in a single pass — no intermediate sparse
matrix is ever assembled:

    tile = Σ_t onehot(rows_t)[block_r, block_n] @ (vals_t ⊙ onehot(cols_t)[block_n, block_m])

Both scatters are one-hot MXU matmuls. Re-compression of the dense tile to
the output CSR (when a sparse output is required) is XLA gather/scan work
performed outside the kernel (ops.py) — assembly is control-flow heavy and
belongs off the MXU (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import formats as fmt


def supports(format: "fmt.Format", space: str) -> bool:
    """Format-dispatch query. The union-add leaves iterate all operands in
    row order, so universe needs the row-window view for EVERY operand;
    the nnz strategy splits the concatenated coordinate stream of the
    three operands, which any unblocked sparse format can feed. Blocked
    operands lower directly via the tile-union leaves (kernels/bcsr.py),
    merging duplicate blocks by summing (br, bc) tiles — lower.py falls
    back to conversion when the three operands' block shapes disagree."""
    return fmt.supports_2d_default(format, space)


def _spadd3_kernel(r1, c1, v1, r2, c2, v2, r3, c3, v3, out_ref, *,
                   block_r: int, block_m: int):
    m = pl.program_id(1)

    def scatter(rows_ref, cols_ref, vals_ref):
        rows = rows_ref[0, :]
        cols = cols_ref[0, :] - m * block_m     # column relative to tile
        vals = vals_ref[0, :]
        bn = rows.shape[0]
        iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_r, bn), 0)
        row_oh = (iota_r == rows[None, :]).astype(vals.dtype)
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (bn, block_m), 1)
        col_oh = (iota_c == cols[:, None]).astype(vals.dtype)
        return row_oh @ (vals[:, None] * col_oh)

    out_ref[0, :, :] = (scatter(r1, c1, v1) + scatter(r2, c2, v2)
                        + scatter(r3, c3, v3))


def spadd3_dense_tiles(rows1, cols1, vals1, rows2, cols2, vals2,
                       rows3, cols3, vals3, *, n_rows: int, n_cols: int,
                       block_r: int = 8, block_m: int = 128,
                       interpret: bool) -> jax.Array:
    """Fused three-way add into dense row tiles.

    Each operand is given in row-block ELL form over the SAME row blocking
    (layout.ell_pack with equal block_r): arrays (n_rblocks, bnnz_t). The
    per-operand bnnz may differ. Returns dense (n_rblocks*block_r, n_cols).

    Note: one grid step scans each operand's whole row-block nnz; operands
    are typically same-density so tiles stay VMEM-sized.
    """
    n_rblocks = rows1.shape[0]
    mpad = -(-n_cols // block_m) * block_m
    grid = (n_rblocks, mpad // block_m)

    def spec(arr):
        return pl.BlockSpec((1, arr.shape[1]), lambda i, mj: (i, 0))

    out = pl.pallas_call(
        functools.partial(_spadd3_kernel, block_r=block_r, block_m=block_m),
        grid=grid,
        in_specs=[spec(rows1), spec(cols1), spec(vals1),
                  spec(rows2), spec(cols2), spec(vals2),
                  spec(rows3), spec(cols3), spec(vals3)],
        out_specs=pl.BlockSpec((1, block_r, block_m), lambda i, mj: (i, 0, mj)),
        out_shape=jax.ShapeDtypeStruct((n_rblocks, block_r, mpad), vals1.dtype),
        interpret=interpret,
    )(rows1, cols1, vals1, rows2, cols2, vals2, rows3, cols3, vals3)
    return out.reshape(n_rblocks * block_r, mpad)[:n_rows, :n_cols]
