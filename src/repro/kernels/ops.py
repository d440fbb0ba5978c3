"""Jit'd wrappers over the sparse kernels — the public compute API.

Every op takes ``impl``:
  - ``"xla"``    — the pure-jnp leaves from ref.py, jitted. Fast on this
                   CPU container; also the lowering used inside pjit'd model
                   code (XLA ops shard/fuse under GSPMD).
  - ``"pallas"`` — the Pallas kernels. The caller states the mode with
                   ``interpret``: ``True`` runs the Pallas interpreter (how
                   the per-kernel allclose tests validate kernel logic on
                   CPU against ref.py), ``False`` compiles for the TPU.
                   There is no default, so no caller lands in the
                   interpreter on a chip without saying so.

Layout packing (CSR → row-block ELL / padded COO) happens here so callers
hand over plain CSR/COO shard arrays.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bcsr import bcsr_sddmm, bcsr_spadd3, bcsr_spmm, bcsr_spmv
from .layout import (bcsr_ell_pack, coo_block_pad, ell_pack,
                     pack_mat_inner_blocks, pack_mat_row_blocks,
                     pack_vec_blocks, resolve_bcsr_tile)
from .sddmm import sddmm_coo
from .spadd3 import spadd3_dense_tiles
from .spmm import spmm_ell
from .spmttkrp import spmttkrp_ell
from .spmv import spmv_coo_phase1, spmv_ell


def _pallas_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` flag every ``impl="pallas"`` call must state."""
    if interpret is None:
        raise TypeError("impl='pallas' needs interpret=True (Pallas "
                        "interpreter) or interpret=False (compiled kernel)")
    return interpret


# ---------------------------------------------------------------------------
# SpMV
# ---------------------------------------------------------------------------

def spmv(pos, crd, vals, c, impl: str = "xla",
         block_r: int = 8, block_n: int = 128,
         interpret: Optional[bool] = None):
    """y(n,) = CSR(pos, crd, vals) @ c."""
    if impl == "xla":
        return jax.jit(ref.leaf_spmv_rows)(jnp.asarray(pos), jnp.asarray(crd),
                                           jnp.asarray(vals), jnp.asarray(c))
    blocks, = ell_pack(np.asarray(pos), np.asarray(crd), np.asarray(vals),
                       block_r=block_r, block_n=block_n)
    y = spmv_ell(jnp.asarray(blocks.rows_rel), jnp.asarray(blocks.crd),
                 jnp.asarray(blocks.vals), jnp.asarray(c),
                 block_r=block_r, block_n=block_n,
                 interpret=_pallas_interpret(interpret))
    return y[: pos.shape[0] - 1]


def spmv_nnz(rows, cols, vals, c, n_rows: int, impl: str = "xla",
             block_n: int = 128, interpret: Optional[bool] = None):
    """y(n,) from sorted COO — the non-zero strategy leaf + merge."""
    if impl == "xla":
        f = jax.jit(partial(ref.leaf_spmv_nnz, max_rows=n_rows))
        return f(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                 jnp.asarray(c))
    r, cc, v, _ = coo_block_pad(np.asarray(rows), np.asarray(cols),
                                np.asarray(vals), block_n=block_n)
    psum, prow = spmv_coo_phase1(jnp.asarray(r), jnp.asarray(cc),
                                 jnp.asarray(v), jnp.asarray(c),
                                 block_n=block_n,
                                 interpret=_pallas_interpret(interpret))
    return jax.ops.segment_sum(psum.ravel(), prow.ravel(),
                               num_segments=n_rows)


# ---------------------------------------------------------------------------
# SpMM
# ---------------------------------------------------------------------------

def spmm(pos, crd, vals, C, impl: str = "xla",
         block_r: int = 8, block_n: int = 128, block_j: int = 128,
         interpret: Optional[bool] = None):
    """Y(n, J) = CSR @ C(K, J)."""
    if impl == "xla":
        return jax.jit(ref.leaf_spmm_rows)(jnp.asarray(pos), jnp.asarray(crd),
                                           jnp.asarray(vals), jnp.asarray(C))
    blocks, = ell_pack(np.asarray(pos), np.asarray(crd), np.asarray(vals),
                       block_r=block_r, block_n=block_n)
    y = spmm_ell(jnp.asarray(blocks.rows_rel), jnp.asarray(blocks.crd),
                 jnp.asarray(blocks.vals), jnp.asarray(C),
                 block_r=block_r, block_n=block_n, block_j=block_j,
                 interpret=_pallas_interpret(interpret))
    return y[: pos.shape[0] - 1]


# ---------------------------------------------------------------------------
# Blocked (BCSR) ops — the direct blocked path's public API. Inputs are the
# block-grid CSR arrays (pos over block-rows, crd block-columns, (nb, br,
# bc) value tiles); dense co-operands are packed into matching blocks here.
# ---------------------------------------------------------------------------

def spmv_bcsr(pos, crd, tiles, c, impl: str = "xla",
              block_R=None, block_nb=None,
              interpret: Optional[bool] = None):
    """y(grid_rows * br,) = BCSR(pos, crd, tiles) @ c — slice to n_rows.

    ``block_R``/``block_nb`` default to the autotuned group shape
    (``resolve_bcsr_tile``, fallback (8, 16))."""
    tiles = np.asarray(tiles)
    bc = tiles.shape[2]
    grid_cols = -(-np.asarray(c).shape[0] // bc)
    c_blk = pack_vec_blocks(np.asarray(c), grid_cols, bc)
    if impl == "xla":
        return jax.jit(ref.leaf_bcsr_spmv_rows)(
            jnp.asarray(pos), jnp.asarray(crd), jnp.asarray(tiles),
            jnp.asarray(c_blk))
    block_R, block_nb = resolve_bcsr_tile(
        np.asarray(pos), (tiles.shape[1], bc), block_R, block_nb)
    blocks = bcsr_ell_pack(np.asarray(pos), np.asarray(crd), tiles,
                           block_R=block_R, block_nb=block_nb)
    y = bcsr_spmv(jnp.asarray(blocks.brows_rel), jnp.asarray(blocks.crd),
                  jnp.asarray(blocks.vals), jnp.asarray(c_blk),
                  block_R=block_R, block_nb=block_nb,
                  interpret=_pallas_interpret(interpret))
    return y[: (pos.shape[0] - 1) * tiles.shape[1]]


def spmm_bcsr(pos, crd, tiles, C, impl: str = "xla",
              block_R=None, block_nb=None,
              interpret: Optional[bool] = None):
    """Y(grid_rows * br, J) = BCSR @ C(K, J) — slice to n_rows.

    ``block_R``/``block_nb`` default to the autotuned group shape
    (``resolve_bcsr_tile``, fallback (8, 16))."""
    tiles = np.asarray(tiles)
    bc = tiles.shape[2]
    C = np.asarray(C)
    grid_cols = -(-C.shape[0] // bc)
    C_blk = pack_mat_row_blocks(C, grid_cols, bc)
    if impl == "xla":
        return jax.jit(ref.leaf_bcsr_spmm_rows)(
            jnp.asarray(pos), jnp.asarray(crd), jnp.asarray(tiles),
            jnp.asarray(C_blk))
    block_R, block_nb = resolve_bcsr_tile(
        np.asarray(pos), (tiles.shape[1], bc), block_R, block_nb)
    blocks = bcsr_ell_pack(np.asarray(pos), np.asarray(crd), tiles,
                           block_R=block_R, block_nb=block_nb)
    y = bcsr_spmm(jnp.asarray(blocks.brows_rel), jnp.asarray(blocks.crd),
                  jnp.asarray(blocks.vals), jnp.asarray(C_blk),
                  block_R=block_R, block_nb=block_nb,
                  interpret=_pallas_interpret(interpret))
    return y[: (pos.shape[0] - 1) * tiles.shape[1]]


def sddmm_bcsr(brow, bcol, tiles, C, D, impl: str = "xla",
               block_nb: int = 16, interpret: Optional[bool] = None):
    """out tiles (nb, br, bc) = tiles ⊙ sampled C(n,K) @ D(K,m) blocks."""
    tiles = np.asarray(tiles)
    br, bc = tiles.shape[1], tiles.shape[2]
    C, D = np.asarray(C), np.asarray(D)
    C_blk = pack_mat_row_blocks(C, -(-C.shape[0] // br), br)
    D_blk = pack_mat_inner_blocks(D, -(-D.shape[1] // bc), bc)
    if impl == "xla":
        return jax.jit(ref.leaf_bcsr_sddmm)(
            jnp.asarray(brow), jnp.asarray(bcol), jnp.asarray(tiles),
            jnp.asarray(C_blk), jnp.asarray(D_blk))
    nb = tiles.shape[0]
    pad = -(-max(nb, 1) // block_nb) * block_nb - nb
    bpad = np.concatenate([np.asarray(brow, np.int32),
                           np.zeros(pad, np.int32)])
    cpad = np.concatenate([np.asarray(bcol, np.int32),
                           np.zeros(pad, np.int32)])
    tpad = np.concatenate([tiles, np.zeros((pad, br, bc), tiles.dtype)])
    out = bcsr_sddmm(jnp.asarray(bpad), jnp.asarray(cpad),
                     jnp.asarray(tpad), jnp.asarray(C_blk),
                     jnp.asarray(D_blk), block_nb=block_nb,
                     interpret=_pallas_interpret(interpret))
    return out[:nb]


def spadd3_bcsr_dense(bcsr1, bcsr2, bcsr3, n_rows: int, n_cols: int,
                      impl: str = "pallas", block_R=None,
                      interpret: Optional[bool] = None):
    """Dense(n, m) = B + C + D from three blocked (pos, crd, tiles)
    triples sharing one block shape — the fused blocked add.

    ``block_R`` defaults to the autotuned group shape for the first
    operand's structure; one value is used for all three packs (the
    fused kernel iterates the three group grids in lockstep)."""
    t1 = np.asarray(bcsr1[2])
    bc = t1.shape[2]
    grid_cols = -(-n_cols // bc)
    if impl == "xla":
        f = jax.jit(partial(ref.leaf_bcsr_spadd3_dense, grid_cols=grid_cols))
        dense = f(*(jnp.asarray(x) for t in (bcsr1, bcsr2, bcsr3)
                    for x in t))
        return dense[:n_rows, :n_cols]
    block_R, _ = resolve_bcsr_tile(np.asarray(bcsr1[0]),
                                   (t1.shape[1], bc), block_R, None)
    packed = [bcsr_ell_pack(np.asarray(p), np.asarray(c), np.asarray(t),
                            block_R=block_R)
              for (p, c, t) in (bcsr1, bcsr2, bcsr3)]
    return bcsr_spadd3(*packed, n_rows=n_rows, n_cols=n_cols,
                       block_R=block_R,
                       interpret=_pallas_interpret(interpret))


# ---------------------------------------------------------------------------
# SDDMM
# ---------------------------------------------------------------------------

def sddmm(rows, cols, vals, C, D, impl: str = "xla", block_n: int = 128,
          interpret: Optional[bool] = None):
    """out_vals(nnz,) = vals ⊙ (C @ D) sampled at (rows, cols)."""
    if impl == "xla":
        return jax.jit(ref.leaf_sddmm_nnz)(
            jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
            jnp.asarray(C), jnp.asarray(D))
    nnz = rows.shape[0]
    r, cc, v, _ = coo_block_pad(np.asarray(rows), np.asarray(cols),
                                np.asarray(vals), block_n=block_n)
    out = sddmm_coo(jnp.asarray(r), jnp.asarray(cc), jnp.asarray(v),
                    jnp.asarray(C), jnp.asarray(D), block_n=block_n,
                    interpret=_pallas_interpret(interpret))
    return out[:nnz]


# ---------------------------------------------------------------------------
# SpAdd3 (fused three-way add)
# ---------------------------------------------------------------------------

def spadd3_dense(csr1, csr2, csr3, n_rows: int, n_cols: int,
                 impl: str = "xla", block_r: int = 8, block_m: int = 128,
                 interpret: Optional[bool] = None):
    """Dense(n, m) = B + C + D from three CSR triples (pos, crd, vals)."""
    if impl == "xla":
        f = jax.jit(partial(ref.leaf_spadd3_dense_rows, n_cols=n_cols))
        return f(*(jnp.asarray(x) for t in (csr1, csr2, csr3) for x in t))
    packed = []
    for pos, crd, vals in (csr1, csr2, csr3):
        blocks, = ell_pack(np.asarray(pos), np.asarray(crd), np.asarray(vals),
                           block_r=block_r, block_n=block_m)
        packed += [jnp.asarray(blocks.rows_rel), jnp.asarray(blocks.crd),
                   jnp.asarray(blocks.vals)]
    return spadd3_dense_tiles(*packed, n_rows=n_rows, n_cols=n_cols,
                              block_r=block_r, block_m=block_m,
                              interpret=_pallas_interpret(interpret))


# ---------------------------------------------------------------------------
# SpTTV — reuses the SpMV ELL kernel over level-1 positions
# ---------------------------------------------------------------------------

def spttv(pos1, crd1, pos2, crd2, vals, c, impl: str = "xla",
          block_r: int = 8, block_n: int = 128,
          interpret: Optional[bool] = None):
    """out_vals aligned with B's (i,j) positions (pattern-preserving)."""
    if impl == "xla":
        return jax.jit(ref.leaf_spttv_rows)(
            jnp.asarray(pos1), jnp.asarray(crd1), jnp.asarray(pos2),
            jnp.asarray(crd2), jnp.asarray(vals), jnp.asarray(c))
    n_ij = crd1.shape[0]
    blocks, = ell_pack(np.asarray(pos2), np.asarray(crd2), np.asarray(vals),
                       block_r=block_r, block_n=block_n)
    out = spmv_ell(jnp.asarray(blocks.rows_rel), jnp.asarray(blocks.crd),
                   jnp.asarray(blocks.vals), jnp.asarray(c),
                   block_r=block_r, block_n=block_n,
                   interpret=_pallas_interpret(interpret))
    return out[:n_ij]


# ---------------------------------------------------------------------------
# SpMTTKRP
# ---------------------------------------------------------------------------

def spmttkrp(pos1, crd1, pos2, crd2, vals, C, D, impl: str = "xla",
             block_r: int = 8, block_n: int = 128,
             interpret: Optional[bool] = None):
    """A(n, L) = B(i,j,k)·C(j,l)·D(k,l) from a CSF shard."""
    if impl == "xla":
        return jax.jit(ref.leaf_spmttkrp_rows)(
            jnp.asarray(pos1), jnp.asarray(crd1), jnp.asarray(pos2),
            jnp.asarray(crd2), jnp.asarray(vals), jnp.asarray(C),
            jnp.asarray(D))
    # flatten CSF: per-nnz (i, j, k); rows = i from pos1∘pos2
    pos1_np, pos2_np = np.asarray(pos1, np.int64), np.asarray(pos2, np.int64)
    i_of_ij = np.repeat(np.arange(pos1_np.shape[0] - 1), np.diff(pos1_np))
    ij_of_nnz = np.repeat(np.arange(pos2_np.shape[0] - 1), np.diff(pos2_np))
    i_per_nnz = i_of_ij[ij_of_nnz]
    j_per_nnz = np.asarray(crd1)[ij_of_nnz]
    # rebuild a pos over i for ell packing
    n_rows = pos1_np.shape[0] - 1
    counts = np.bincount(i_per_nnz, minlength=n_rows)
    pos_i = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=pos_i[1:])
    blocks, kk = ell_pack(pos_i, j_per_nnz.astype(np.int32),
                          np.asarray(vals), block_r=block_r,
                          block_n=block_n,
                          extra=(np.asarray(crd2, np.int32),))
    out = spmttkrp_ell(jnp.asarray(blocks.rows_rel), jnp.asarray(blocks.crd),
                       jnp.asarray(kk), jnp.asarray(blocks.vals),
                       jnp.asarray(C), jnp.asarray(D),
                       block_r=block_r, block_n=block_n,
                       interpret=_pallas_interpret(interpret))
    return out[:n_rows]
