"""Pallas TPU blocked (BCSR) kernels — the direct blocked execution path.

SpDISTAL's thesis is that compiling for the *declared* format beats
converting (paper §IV, §VI); for blocked formats the declared structure is
exactly what the MXU wants: every stored position carries a dense
``(br, bc)`` value tile, so the leaf's inner op is a dense tile matmul
instead of the scalarized gather+segment-sum the bcsr→csr fallback paid.

All four 2-D families get a blocked leaf, each a lift of its scalar kernel
to block granularity over the ``layout.bcsr_ell_pack`` arrays:

- :func:`bcsr_spmv`   — grid (block-row group × block chunk); per block a
  ``(br, bc) @ (bc,)`` tile matvec, then the one-hot segmented-reduction
  trick from ``spmv.py`` applied to BLOCK-rows:
  ``out[block_R, br] += onehot(brows_rel) @ prod[chunk, br]``.
- :func:`bcsr_spmm`   — same grid; per block a ``(br, bc) @ (bc, J)`` MXU
  matmul against the j-blocked dense operand.
- :func:`bcsr_sddmm`  — flat block-chunk grid; sampled
  ``C[brow] @ D[bcol]`` tile products, output tiles aligned with the
  stored block positions (pattern-preserving, §V-B).
- :func:`bcsr_spadd3` — dense block-row-group accumulation of three
  operands' tile streams via row/col one-hots (the ``spadd3.py`` scatter at
  block granularity).

The dense co-operands arrive pre-reshaped into blocks matching the sparse
operand's blocking (``pack_*`` helpers below); boundary blocks of a
non-divisible shape keep their zero padding, which multiplies away and is
sliced off by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layout import (bcsr_ell_pack, pack_mat_inner_blocks,
                     pack_mat_row_blocks, pack_vec_blocks)

# Format dispatch for these leaves lives in the kernel-family modules
# (spmv/spmm/sddmm/spadd3 supports() via formats.supports_2d_default's
# blocked clause) — this module only provides the kernels.


# ---------------------------------------------------------------------------
# SpMV — block-row-group × block-chunk grid
# ---------------------------------------------------------------------------

def _bcsr_spmv_kernel(brows_ref, crd_ref, bvals_ref, c_ref, out_ref, *,
                      block_R: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    brows = brows_ref[0]                        # (chunk,) relative block-rows
    crd = crd_ref[0]                            # (chunk,) block-columns
    bv = bvals_ref[0]                           # (chunk, br, bc) tiles
    cg = jnp.take(c_ref[...], crd, axis=0)      # (chunk, bc) VMEM gather
    prod = jnp.einsum("nrc,nc->nr", bv, cg)     # per-tile (br,bc)@(bc,)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_R, brows.shape[0]), 0)
    onehot = (iota == brows[None, :]).astype(prod.dtype)
    out_ref[0] += onehot @ prod                 # block-granular segmented sum


def bcsr_spmv(brows_rel: jax.Array, crd: jax.Array, bvals: jax.Array,
              c_blk: jax.Array, *, block_R: int = 8, block_nb: int = 16,
              interpret: bool) -> jax.Array:
    """Returns y of shape (n_groups * block_R * br,).

    Inputs are ``layout.bcsr_ell_pack`` arrays; ``c_blk`` is the dense
    vector in column blocks (grid_cols, bc)."""
    n_groups, bnnz = brows_rel.shape
    br = bvals.shape[2]
    assert bnnz % block_nb == 0
    grid = (n_groups, bnnz // block_nb)
    out = pl.pallas_call(
        functools.partial(_bcsr_spmv_kernel, block_R=block_R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_nb), lambda g, n: (g, n)),
            pl.BlockSpec((1, block_nb), lambda g, n: (g, n)),
            pl.BlockSpec((1, block_nb) + bvals.shape[2:],
                         lambda g, n: (g, n, 0, 0)),
            pl.BlockSpec(c_blk.shape, lambda g, n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_R, br), lambda g, n: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, block_R, br), bvals.dtype),
        interpret=interpret,
    )(brows_rel, crd, bvals, c_blk)
    return out.reshape(n_groups * block_R * br)


# ---------------------------------------------------------------------------
# SpMM — per block a dense (br, bc) @ (bc, J) MXU matmul
# ---------------------------------------------------------------------------

def _bcsr_spmm_kernel(brows_ref, crd_ref, bvals_ref, c_ref, out_ref, *,
                      block_R: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    brows = brows_ref[0]
    crd = crd_ref[0]
    bv = bvals_ref[0]                            # (chunk, br, bc)
    cg = jnp.take(c_ref[...], crd, axis=0)       # (chunk, bc, J)
    prod = jnp.einsum("nrc,ncj->nrj", bv, cg)    # dense tile matmuls (MXU)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_R, brows.shape[0]), 0)
    onehot = (iota == brows[None, :]).astype(prod.dtype)
    out_ref[0] += jnp.einsum("Rn,nrj->Rrj", onehot, prod)


def bcsr_spmm(brows_rel: jax.Array, crd: jax.Array, bvals: jax.Array,
              C_blk: jax.Array, *, block_R: int = 8, block_nb: int = 16,
              interpret: bool) -> jax.Array:
    """Returns Y of shape (n_groups * block_R * br, J). ``C_blk`` is the
    dense operand in row blocks (grid_cols, bc, J); J stays VMEM-resident
    (j-block with multiple calls for very wide J, see spmm.py)."""
    n_groups, bnnz = brows_rel.shape
    br = bvals.shape[2]
    J = C_blk.shape[2]
    assert bnnz % block_nb == 0
    grid = (n_groups, bnnz // block_nb)
    out = pl.pallas_call(
        functools.partial(_bcsr_spmm_kernel, block_R=block_R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_nb), lambda g, n: (g, n)),
            pl.BlockSpec((1, block_nb), lambda g, n: (g, n)),
            pl.BlockSpec((1, block_nb) + bvals.shape[2:],
                         lambda g, n: (g, n, 0, 0)),
            pl.BlockSpec(C_blk.shape, lambda g, n: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_R, br, J), lambda g, n: (g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, block_R, br, J),
                                       bvals.dtype),
        interpret=interpret,
    )(brows_rel, crd, bvals, C_blk)
    return out.reshape(n_groups * block_R * br, J)


# ---------------------------------------------------------------------------
# SDDMM — sampled C-row-block @ D-col-block tile products
# ---------------------------------------------------------------------------

def _bcsr_sddmm_kernel(brow_ref, bcol_ref, bvals_ref, c_ref, d_ref, out_ref):
    brow = brow_ref[0]
    bcol = bcol_ref[0]
    bv = bvals_ref[0]                            # (chunk, br, bc)
    cg = jnp.take(c_ref[...], brow, axis=0)      # (chunk, br, K)
    dg = jnp.take(d_ref[...], bcol, axis=0)      # (chunk, K, bc)
    out_ref[0] = bv * jnp.einsum("nrk,nkc->nrc", cg, dg)


def bcsr_sddmm(brow: jax.Array, bcol: jax.Array, bvals: jax.Array,
               C_blk: jax.Array, D_blk: jax.Array, *, block_nb: int = 16,
               interpret: bool) -> jax.Array:
    """Returns out tiles (n_blocks_padded, br, bc) aligned with the stored
    block positions. ``brow``/``bcol`` are GLOBAL block coordinates
    (clipped for padding slots — their tiles are zero); ``C_blk``
    (grid_rows, br, K), ``D_blk`` (grid_cols, K, bc)."""
    nb = brow.shape[0]
    assert nb % block_nb == 0
    n_chunks = nb // block_nb
    br, bc = bvals.shape[1], bvals.shape[2]
    b2 = brow.reshape(n_chunks, block_nb)
    c2 = bcol.reshape(n_chunks, block_nb)
    v2 = bvals.reshape(n_chunks, block_nb, br, bc)
    out = pl.pallas_call(
        _bcsr_sddmm_kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((1, block_nb), lambda g: (g, 0)),
            pl.BlockSpec((1, block_nb), lambda g: (g, 0)),
            pl.BlockSpec((1, block_nb, br, bc), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec(C_blk.shape, lambda g: (0, 0, 0)),
            pl.BlockSpec(D_blk.shape, lambda g: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_nb, br, bc), lambda g: (g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, block_nb, br, bc),
                                       bvals.dtype),
        interpret=interpret,
    )(b2, c2, v2, C_blk, D_blk)
    return out.reshape(nb, br, bc)


# ---------------------------------------------------------------------------
# SpAdd3 — dense block-row-group accumulation of three tile streams
# ---------------------------------------------------------------------------

def _bcsr_spadd3_kernel(r1, c1, v1, r2, c2, v2, r3, c3, v3, out_ref, *,
                        block_R: int, grid_cols: int):
    def scatter(brows_ref, bcols_ref, tiles_ref):
        brows = brows_ref[0]
        bcols = bcols_ref[0]
        tiles = tiles_ref[0]                     # (chunk, br, bc)
        n = brows.shape[0]
        iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_R, n), 0)
        row_oh = (iota_r == brows[None, :]).astype(tiles.dtype)
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (n, grid_cols), 1)
        col_oh = (iota_c == bcols[:, None]).astype(tiles.dtype)
        # both scatters are one-hot contractions at block granularity
        return jnp.einsum("Rn,nG,nrc->RrGc", row_oh, col_oh, tiles)

    out_ref[0] = (scatter(r1, c1, v1) + scatter(r2, c2, v2)
                  + scatter(r3, c3, v3))


def bcsr_spadd3(packed1, packed2, packed3, *, n_rows: int, n_cols: int,
                block_R: int = 8, interpret: bool) -> jax.Array:
    """Fused three-way blocked add into dense rows.

    Each ``packed`` is a ``layout.bcsr_ell_pack`` result over the SAME
    block-row grouping; returns dense (n_rows, n_cols) with the block
    padding sliced off."""
    n_groups = packed1.brows_rel.shape[0]
    br, bc = packed1.vals.shape[2], packed1.vals.shape[3]
    grid_cols = -(-n_cols // bc)

    def specs(p):
        chunk = p.brows_rel.shape[1]
        return [
            pl.BlockSpec((1, chunk), lambda g: (g, 0)),
            pl.BlockSpec((1, chunk), lambda g: (g, 0)),
            pl.BlockSpec((1, chunk, br, bc), lambda g: (g, 0, 0, 0)),
        ]

    out = pl.pallas_call(
        functools.partial(_bcsr_spadd3_kernel, block_R=block_R,
                          grid_cols=grid_cols),
        grid=(n_groups,),
        in_specs=specs(packed1) + specs(packed2) + specs(packed3),
        out_specs=pl.BlockSpec((1, block_R, br, grid_cols, bc),
                               lambda g: (g, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, block_R, br, grid_cols, bc),
                                       packed1.vals.dtype),
        interpret=interpret,
    )(packed1.brows_rel, packed1.crd, packed1.vals,
      packed2.brows_rel, packed2.crd, packed2.vals,
      packed3.brows_rel, packed3.crd, packed3.vals)
    dense = out.reshape(n_groups * block_R * br, grid_cols * bc)
    return dense[:n_rows, :n_cols]
