"""Pallas TPU SDDMM kernel — ``A(i,j) = B(i,j) · C(i,k) · D(k,j)``.

The paper's SDDMM uses a *non-zero based* algorithm and data distribution
(§VI-A: "achieves near perfect speedup due to its load balanced approach").
The leaf here matches: a flat nnz-block grid over the equal-nnz COO shard;
each step gathers the C rows / D columns its coordinates touch and forms the
sampled inner products on the VPU:

    out[nnz_blk] = vals ⊙ Σ_k C[rows, k] · D[k, cols]

The k reduction stays in registers (C gathered (block_n, K), D passed
pre-transposed so its gather is also row-major). Output is dense in the
position space — the paper's "sparsity pattern of the input is preserved in
the output" fast path (§V-B), so no assembly is needed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import formats as fmt


def supports(format: "fmt.Format", space: str) -> bool:
    """Format-dispatch query. SDDMM is pattern-preserving: the non-zero
    leaf is storage-order agnostic (per-position sampled products), so any
    unblocked sparse format works under nnz — including CSC, whose vals
    simply stay in column-major position order. Universe needs the
    row-window view. BCSR lowers directly to sampled block products
    (kernels/bcsr.py), the output tiles staying aligned with the stored
    block positions."""
    return fmt.supports_2d_default(format, space)


def _sddmm_kernel(rows_ref, cols_ref, vals_ref, c_ref, dt_ref, out_ref):
    rows = rows_ref[0, :]
    cols = cols_ref[0, :]
    vals = vals_ref[0, :]
    cg = jnp.take(c_ref[...], rows, axis=0)    # (block_n, K)
    dg = jnp.take(dt_ref[...], cols, axis=0)   # (block_n, K)  (D.T gather)
    out_ref[0, :] = vals * jnp.sum(cg * dg, axis=1)


def sddmm_coo(rows: jax.Array, cols: jax.Array, vals: jax.Array,
              C: jax.Array, D: jax.Array, *, block_n: int = 128,
              interpret: bool) -> jax.Array:
    """Returns out_vals (nnz,), aligned with the COO positions.

    ``rows``/``cols`` may contain out-of-range sentinels for padding; their
    vals are zero so the gather result is multiplied away (indices are
    clipped to stay in range).
    """
    nnz = rows.shape[0]
    assert nnz % block_n == 0
    nb = nnz // block_n
    n, K = C.shape
    m = D.shape[1]
    Dt = D.T  # row-major gather on TPU
    rows_c = jnp.clip(rows, 0, n - 1).reshape(nb, block_n)
    cols_c = jnp.clip(cols, 0, m - 1).reshape(nb, block_n)
    v2 = vals.reshape(nb, block_n)
    out = pl.pallas_call(
        _sddmm_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec((1, block_n), lambda b: (b, 0)),
            pl.BlockSpec(C.shape, lambda b: (0, 0)),
            pl.BlockSpec(Dt.shape, lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_n), vals.dtype),
        interpret=interpret,
    )(rows_c, cols_c, v2, C, Dt)
    return out.reshape(nnz)
