"""Pure-jnp reference oracles for every SpDISTAL leaf kernel.

Two families:

1. **Dense oracles** (`dense_*`) — straight jnp.einsum on densified inputs.
   These define semantics for the paper's six evaluated expressions and are
   what every kernel (XLA leaf or Pallas) is asserted against.

2. **Shard leaves** (`leaf_*`) — per-shard, statically-shaped jnp
   implementations operating on the padded shard layouts produced by
   `core.partition`. These are the "generated leaf kernel" equivalents used
   by the simulation backend; Pallas kernels replace them on TPU.

Leaves consume **packed level arrays**, never format descriptors: the
positional arguments are the materialized regions of a level-tree walk
(core/levels.py) — ``pos``/``crd`` pairs for grouped walks, per-dimension
coordinate columns for flat walks, ``(br, bc)`` tile stacks for block
levels. Which format produced a walk is invisible here: a transpose-walked
CSC shard and a CSR shard feed the SAME leaf, which is what keeps the leaf
set at one per (expression × strategy × walk family) instead of one per
format.

Padding convention: padded nnz slots have ``vals == 0`` and ``crd == 0`` so
multiplicative kernels are unaffected; padded rows have empty pos ranges.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Dense oracles (semantics of the paper's evaluation kernels, §VI-A)
# ---------------------------------------------------------------------------

def dense_spmv(B, c):
    return jnp.einsum("ij,j->i", B, c)


def dense_spmm(B, C):
    return jnp.einsum("ik,kj->ij", B, C)


def dense_spadd3(B, C, D):
    return B + C + D


def dense_sddmm(Bpat, C, D):
    """A(i,j) = B(i,j) * C(i,k) * D(k,j) — sample dense product at B's nnz."""
    return Bpat * jnp.einsum("ik,kj->ij", C, D)


def dense_spttv(B, c):
    return jnp.einsum("ijk,k->ij", B, c)


def dense_spmttkrp(B, C, D):
    return jnp.einsum("ijk,jl,kl->il", B, C, D)


# ---------------------------------------------------------------------------
# Shard-leaf helpers
# ---------------------------------------------------------------------------

def rows_from_pos(pos: jnp.ndarray, n_positions: int) -> jnp.ndarray:
    """Expand a local pos array to a per-position parent index.

    ``pos``: (R+1,) monotone int32. Returns (n_positions,) row ids; padded
    positions (>= pos[-1]) clip to the last row, harmless since their vals
    are zero."""
    p = jnp.arange(n_positions, dtype=pos.dtype)
    r = jnp.searchsorted(pos, p, side="right") - 1
    return jnp.clip(r, 0, pos.shape[0] - 2)


# ---------------------------------------------------------------------------
# Shard leaves — rows (universe) strategy
# ---------------------------------------------------------------------------

def leaf_spmv_rows(pos, crd, vals, c):
    """y_local(R,) from a CSR row shard; c replicated (paper Fig. 9b leaf)."""
    R = pos.shape[0] - 1
    rows = rows_from_pos(pos, crd.shape[0])
    prod = vals * jnp.take(c, crd, axis=0)
    return jax.ops.segment_sum(prod, rows, num_segments=R)


def leaf_spmv_nnz(rows_local, cols, vals, c, max_rows):
    """y_local(max_rows,) from an equal-nnz COO shard; rows_local already
    rebased to the shard's root interval (overlap handled by caller
    reduction — paper §II-D non-zero algorithm)."""
    prod = vals * jnp.take(c, cols, axis=0)
    return jax.ops.segment_sum(prod, rows_local, num_segments=max_rows)


def leaf_spmm_rows(pos, crd, vals, C):
    """Y_local(R, J) = local CSR @ C, C(K, J) replicated."""
    R = pos.shape[0] - 1
    rows = rows_from_pos(pos, crd.shape[0])
    gathered = jnp.take(C, crd, axis=0)            # (N, J)
    prod = vals[:, None] * gathered
    return jax.ops.segment_sum(prod, rows, num_segments=R)


def leaf_spmm_nnz(rows_local, cols, vals, C, max_rows):
    gathered = jnp.take(C, cols, axis=0)
    prod = vals[:, None] * gathered
    return jax.ops.segment_sum(prod, rows_local, num_segments=max_rows)


def leaf_sddmm_nnz(rows, cols, vals, C, D):
    """out_vals(N,) = vals * <C[rows,:], D[:,cols]> — the fused SDDMM leaf
    (non-zero distributed algorithm, paper §VI-A)."""
    Cg = jnp.take(C, rows, axis=0)                 # (N, K)
    Dg = jnp.take(D, cols, axis=1).T               # (N, K)
    return vals * jnp.sum(Cg * Dg, axis=1)


def leaf_sddmm_rows(pos, crd, vals, C_local, D):
    """Row-window SDDMM leaf: B given as a local CSR/densified-root shard,
    C's matching row block local, D replicated. Output vals stay aligned
    with B's shard positions (pattern-preserving, paper §V-B)."""
    rows = rows_from_pos(pos, crd.shape[0])
    Cg = jnp.take(C_local, rows, axis=0)           # (N, K) local rows
    Dg = jnp.take(D, crd, axis=1).T                # (N, K)
    return vals * jnp.sum(Cg * Dg, axis=1)


def leaf_spadd3_rows(pos1, crd1, v1, pos2, crd2, v2, pos3, crd3, v3, n_cols):
    """Fused three-way sparse add over a row shard.

    Two-phase union assembly (Chou et al. [28]) fused across all three
    operands: lexsort concatenated (row, col) pairs, dedupe, segment-sum.
    Output is a padded union COO (rows, cols, vals, count). Static output
    size = N1+N2+N3. int32 throughout (TPU-friendly; no fused int64 key)."""
    R = pos1.shape[0] - 1
    rows = jnp.concatenate([
        rows_from_pos(pos1, crd1.shape[0]),
        rows_from_pos(pos2, crd2.shape[0]),
        rows_from_pos(pos3, crd3.shape[0]),
    ])
    cols = jnp.concatenate([crd1, crd2, crd3])
    vals = jnp.concatenate([v1, v2, v3])
    # padded slots: vals==0; push them past every valid row so they sort last
    valid = jnp.concatenate([
        jnp.arange(crd1.shape[0]) < (pos1[-1] - pos1[0]),
        jnp.arange(crd2.shape[0]) < (pos2[-1] - pos2[0]),
        jnp.arange(crd3.shape[0]) < (pos3[-1] - pos3[0]),
    ])
    rows = jnp.where(valid, rows, R).astype(jnp.int32)
    order = jnp.lexsort((cols, rows))
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    valid_s = valid[order]
    newseg = jnp.concatenate([
        jnp.array([True]),
        (rows_s[1:] != rows_s[:-1]) | (cols_s[1:] != cols_s[:-1]),
    ])
    n = rows.shape[0]
    seg_id = jnp.cumsum(newseg) - 1
    out_vals = jax.ops.segment_sum(vals_s, seg_id, num_segments=n)
    first = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32), seg_id,
                                num_segments=n)
    first = jnp.clip(first, 0, n - 1)
    out_rows = jnp.take(rows_s, first)
    out_cols = jnp.take(cols_s, first)
    count = jnp.sum((newseg & valid_s).astype(jnp.int32))
    in_range = jnp.arange(n) < count
    out_rows = jnp.where(in_range, out_rows, 0).astype(jnp.int32)
    out_cols = jnp.where(in_range, out_cols, 0).astype(jnp.int32)
    out_vals = jnp.where(in_range, out_vals, 0)
    return out_rows, out_cols, out_vals, count


def leaf_spadd_union_chunk(rows, cols, vals, count, n_rows):
    """Per-chunk union leaf for the non-zero SpAdd strategy: the chunk is a
    slice of the CONCATENATED coordinate stream of all addends (the
    coordinate-position loop of an addition). Same two-phase union as
    leaf_spadd3_rows, over global rows; duplicates that straddle chunk
    boundaries merge in the host-side assembly's dedupe."""
    n = rows.shape[0]
    valid = jnp.arange(n) < count
    rows = jnp.where(valid, rows, n_rows).astype(jnp.int32)
    order = jnp.lexsort((cols, rows))
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    valid_s = valid[order]
    newseg = jnp.concatenate([
        jnp.array([True]),
        (rows_s[1:] != rows_s[:-1]) | (cols_s[1:] != cols_s[:-1]),
    ])
    seg_id = jnp.cumsum(newseg) - 1
    out_vals = jax.ops.segment_sum(vals_s, seg_id, num_segments=n)
    first = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32), seg_id,
                                num_segments=n)
    first = jnp.clip(first, 0, n - 1)
    out_rows = jnp.take(rows_s, first)
    out_cols = jnp.take(cols_s, first)
    out_count = jnp.sum((newseg & valid_s).astype(jnp.int32))
    in_range = jnp.arange(n) < out_count
    out_rows = jnp.where(in_range, out_rows, 0).astype(jnp.int32)
    out_cols = jnp.where(in_range, out_cols, 0).astype(jnp.int32)
    out_vals = jnp.where(in_range, out_vals, 0)
    return out_rows, out_cols, out_vals, out_count


def leaf_spadd3_dense_rows(pos1, crd1, v1, pos2, crd2, v2, pos3, crd3, v3,
                           n_cols):
    """Dense-row-accumulate variant (the Pallas-kernel contract): scatter all
    three operands into dense local rows. Used when the output is consumed
    densely or re-compressed by XLA."""
    R = pos1.shape[0] - 1
    out = jnp.zeros((R, n_cols), dtype=v1.dtype)
    for pos, crd, v in ((pos1, crd1, v1), (pos2, crd2, v2), (pos3, crd3, v3)):
        rows = rows_from_pos(pos, crd.shape[0])
        out = out.at[rows, crd].add(v)
    return out


# ---------------------------------------------------------------------------
# Blocked (BCSR) leaves — every stored position carries a dense (br, bc)
# value tile, so the inner op per position is a dense tile matmul (the MXU
# contract the direct blocked path compiles to). Dense co-operands arrive
# pre-reshaped into matching blocks (kernels.bcsr pack_* helpers); boundary
# blocks keep their zero padding, which multiplies away.
# ---------------------------------------------------------------------------

# The TPU's default matmul precision runs float32 operands as bf16 passes;
# HIGHEST keeps the tile matmuls float32 on the MXU, as they are on the CPU.
_F32 = jax.lax.Precision.HIGHEST

def leaf_bcsr_spmv_rows(pos, crd, bvals, c_blk):
    """y_local(R*br,) from a blocked row shard: per stored block a
    (br, bc) @ (bc,) tile matvec, segment-summed over block-rows.
    ``c_blk`` is the dense vector in column blocks, (grid_cols, bc)."""
    R = pos.shape[0] - 1
    brow = rows_from_pos(pos, crd.shape[0])
    cg = jnp.take(c_blk, crd, axis=0)                  # (NB, bc)
    prod = jnp.einsum("nrc,nc->nr", bvals, cg, precision=_F32)
    acc = jax.ops.segment_sum(prod, brow, num_segments=R)
    return acc.reshape(-1)


def leaf_bcsr_spmv_nnz(brow_local, bcol, bvals, c_blk, max_brows):
    """Equal-stored-block shard: block-rows already rebased to the shard's
    block-row window; padding blocks have zero tiles."""
    cg = jnp.take(c_blk, bcol, axis=0)
    prod = jnp.einsum("nrc,nc->nr", bvals, cg, precision=_F32)
    acc = jax.ops.segment_sum(prod, brow_local, num_segments=max_brows)
    return acc.reshape(-1)


def leaf_bcsr_spmm_rows(pos, crd, bvals, C_blk):
    """Y_local(R*br, J): per stored block a dense (br, bc) @ (bc, J)
    matmul. ``C_blk`` is the dense operand in row blocks, (grid_cols, bc, J)."""
    R = pos.shape[0] - 1
    brow = rows_from_pos(pos, crd.shape[0])
    cg = jnp.take(C_blk, crd, axis=0)                  # (NB, bc, J)
    prod = jnp.einsum("nrc,ncj->nrj", bvals, cg, precision=_F32)
    acc = jax.ops.segment_sum(prod, brow, num_segments=R)
    return acc.reshape(-1, cg.shape[-1])


def leaf_bcsr_spmm_nnz(brow_local, bcol, bvals, C_blk, max_brows):
    cg = jnp.take(C_blk, bcol, axis=0)
    prod = jnp.einsum("nrc,ncj->nrj", bvals, cg, precision=_F32)
    acc = jax.ops.segment_sum(prod, brow_local, num_segments=max_brows)
    return acc.reshape(-1, cg.shape[-1])


def leaf_bcsr_sddmm(brow, bcol, bvals, C_blk, D_blk):
    """out tiles (NB, br, bc) = bvals ⊙ (C row-block @ D col-block), the
    pattern-preserving sampled product at block granularity. ``C_blk``
    (n_brow_blocks, br, K) row blocks — shard-local under rows, the full
    grid under nnz; ``D_blk`` (grid_cols, K, bc) column blocks."""
    Cg = jnp.take(C_blk, brow, axis=0)                 # (NB, br, K)
    Dg = jnp.take(D_blk, bcol, axis=0)                 # (NB, K, bc)
    sampled = jnp.einsum("nrk,nkc->nrc", Cg, Dg, precision=_F32)
    return bvals * sampled


def _tile_union(brows, bcols, tiles, valid):
    """Shared two-phase union over (block-row, block-col) keyed TILE
    streams: lexsort, segment-sum duplicate tiles, compact. ``brows`` must
    already carry the past-every-valid sentinel on invalid slots."""
    if brows.shape[0] == 0:      # statically-empty stream (empty operands)
        return (brows.astype(jnp.int32), bcols.astype(jnp.int32), tiles,
                jnp.zeros((), jnp.int32))
    order = jnp.lexsort((bcols, brows))
    r_s, c_s, t_s = brows[order], bcols[order], tiles[order]
    valid_s = valid[order]
    newseg = jnp.concatenate([
        jnp.array([True]),
        (r_s[1:] != r_s[:-1]) | (c_s[1:] != c_s[:-1]),
    ])
    n = brows.shape[0]
    seg_id = jnp.cumsum(newseg) - 1
    out_tiles = jax.ops.segment_sum(t_s, seg_id, num_segments=n)
    first = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32), seg_id,
                                num_segments=n)
    first = jnp.clip(first, 0, n - 1)
    out_r = jnp.take(r_s, first)
    out_c = jnp.take(c_s, first)
    count = jnp.sum((newseg & valid_s).astype(jnp.int32))
    in_range = jnp.arange(n) < count
    out_r = jnp.where(in_range, out_r, 0).astype(jnp.int32)
    out_c = jnp.where(in_range, out_c, 0).astype(jnp.int32)
    out_tiles = jnp.where(in_range[:, None, None], out_tiles, 0)
    return out_r, out_c, out_tiles, count


def leaf_bcsr_spadd3_rows(pos1, crd1, t1, pos2, crd2, t2, pos3, crd3, t3):
    """Fused three-way blocked add over a block-row shard: union of the
    three block coordinate streams, duplicate blocks merged by summing
    their (br, bc) tiles — no scalarization. Returns a padded union block
    stream (brows_local, bcols, tiles, count)."""
    R = pos1.shape[0] - 1
    brows = jnp.concatenate([
        rows_from_pos(pos1, crd1.shape[0]),
        rows_from_pos(pos2, crd2.shape[0]),
        rows_from_pos(pos3, crd3.shape[0]),
    ])
    bcols = jnp.concatenate([crd1, crd2, crd3])
    tiles = jnp.concatenate([t1, t2, t3])
    valid = jnp.concatenate([
        jnp.arange(crd1.shape[0]) < (pos1[-1] - pos1[0]),
        jnp.arange(crd2.shape[0]) < (pos2[-1] - pos2[0]),
        jnp.arange(crd3.shape[0]) < (pos3[-1] - pos3[0]),
    ])
    brows = jnp.where(valid, brows, R).astype(jnp.int32)
    return _tile_union(brows, bcols, tiles, valid)


def leaf_bcsr_spadd_union_chunk(brows, bcols, tiles, count, n_brows):
    """Per-chunk union leaf for the blocked nnz SpAdd strategy: the chunk
    slices the concatenated BLOCK stream of all addends; duplicate blocks
    straddling chunk boundaries merge in the host assembly
    (Tensor.from_blocks dedupe)."""
    n = brows.shape[0]
    valid = jnp.arange(n) < count
    brows = jnp.where(valid, brows, n_brows).astype(jnp.int32)
    return _tile_union(brows, bcols, tiles, valid)


def leaf_bcsr_spadd3_dense(pos1, crd1, t1, pos2, crd2, t2, pos3, crd3, t3,
                           grid_cols):
    """Dense-accumulate variant of the blocked add (the XLA counterpart of
    the bcsr_spadd3 Pallas kernel): scatter-add all three tile streams into
    a dense block grid, return row-major dense (R*br, grid_cols*bc)."""
    R = pos1.shape[0] - 1
    br, bc = t1.shape[1], t1.shape[2]
    out = jnp.zeros((R, grid_cols, br, bc), dtype=t1.dtype)
    for pos, crd, t in ((pos1, crd1, t1), (pos2, crd2, t2), (pos3, crd3, t3)):
        brow = rows_from_pos(pos, crd.shape[0])
        out = out.at[brow, crd].add(t)
    return out.transpose(0, 2, 1, 3).reshape(R * br, grid_cols * bc)


def leaf_spttv_rows(pos1, crd1, pos2, crd2, vals, c):
    """A(i,j) = B(i,j,k)·c(k) over a CSF row shard. Output sparsity equals
    B's (i,j) pattern (paper §V-B) → returns vals aligned with level-1
    positions."""
    n_ij = crd1.shape[0]
    ij_of_nnz = rows_from_pos(pos2, crd2.shape[0])
    prod = vals * jnp.take(c, crd2, axis=0)
    return jax.ops.segment_sum(prod, ij_of_nnz, num_segments=n_ij)


def leaf_spttv_nnz(ij_local, k, vals, c, max_ij):
    prod = vals * jnp.take(c, k, axis=0)
    return jax.ops.segment_sum(prod, ij_local, num_segments=max_ij)


def leaf_spmttkrp_rows(pos1, crd1, pos2, crd2, vals, C, D):
    """A(i,l) = B(i,j,k)·C(j,l)·D(k,l) over a CSF row shard → (R, L)."""
    R = pos1.shape[0] - 1
    ij_of_nnz = rows_from_pos(pos2, crd2.shape[0])   # level-1 position per nnz
    i_of_ij = rows_from_pos(pos1, crd1.shape[0])     # row per level-1 position
    j = jnp.take(crd1, ij_of_nnz, axis=0)
    i = jnp.take(i_of_ij, ij_of_nnz, axis=0)
    contrib = vals[:, None] * jnp.take(C, j, axis=0) * jnp.take(D, crd2, axis=0)
    return jax.ops.segment_sum(contrib, i, num_segments=R)


def leaf_spmttkrp_nnz(i_local, j, k, vals, C, D, max_rows):
    contrib = vals[:, None] * jnp.take(C, j, axis=0) * jnp.take(D, k, axis=0)
    return jax.ops.segment_sum(contrib, i_local, num_segments=max_rows)
