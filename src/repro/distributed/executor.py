"""SPMD executor for lowered sparse kernels — the shard_map backend.

`core.lower` runs kernels through a vmap simulation (single-process
correctness). This module runs the SAME leaf functions under
`jax.shard_map` on a real mesh: the stacked shard arrays' leading color
axis is sharded over the machine axis, replicated operands broadcast, and
the paper's ``communicate`` becomes explicit collectives
(distributed/collectives.py). The multi-device test suite launches this
under ``--xla_force_host_platform_device_count`` to prove the distributed
loop structure is coherent without real hardware.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.cache import LRUCache, avals_key
from ..core.lower import LoweredKernel, _jit_leaf
from ..core.tdn import Machine
from ..kernels import ref as K
from ..runtime import telemetry
from ..kernels.layout import (pack_mat_inner_blocks, pack_mat_row_blocks,
                              pack_rowwindow_blocks, pack_vec_blocks)
from .mesh import machine_to_mesh

# Compiled shard_map executables, keyed like core.lower's runner cache
# (builder name, mesh, axis, static trace constants, shard avals).
# Re-building the SPMD executor after a re-lower then reuses the jitted
# callable — jax's compilation cache hits instead of re-tracing the
# collective program.
_SPMD_RUN_CACHE = LRUCache(capacity=64)
SPMD_RUN_STATS = _SPMD_RUN_CACHE.stats


def set_spmd_cache_capacity(capacity: int) -> None:
    _SPMD_RUN_CACHE.set_capacity(capacity)


def clear_spmd_cache() -> None:
    _SPMD_RUN_CACHE.clear()


def _mesh_key(mesh: Mesh):
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def _spmd_runner(name, mesh, axis, static, in_specs, arrays, build):
    """Return the jitted shard_map executable for a builder — reusing a
    cached one when (builder, mesh, axis, statics, shard avals) match —
    and the builder's arrays placed ONCE on the mesh, each under its
    ``in_specs`` entry as a NamedSharding. Every call then runs on arrays
    that already live where the program reads them: nothing lands on the
    first device and reshards inside the program per call. As in
    ``core.lower._runner``, the executable is named by its leaf and called
    through the traced runner boundary."""
    key = (name, _mesh_key(mesh), axis, tuple(static), avals_key(arrays))
    run = _SPMD_RUN_CACHE.get_or_build(key, lambda: _jit_leaf(build(), name))
    placed = tuple(jax.device_put(a, NamedSharding(mesh, s))
                   for a, s in zip(arrays, in_specs))
    return run, placed


def _assemble_vals(total, out_vals, arrays, vals_bounds):
    """Host assembly of per-color leaf VALUE outputs into the global value
    region (scalar slots or (br, bc) tiles alike). Ordered walks fill by
    value-space interval; transpose-walked shards carry a ``val_idx``
    permutation in their packed level arrays and scatter home by stored
    position — the builders never ask which format produced the walk."""
    flat = np.zeros((total,) + out_vals.shape[2:], np.float32)
    cnt = np.asarray(arrays["nnz_count"])
    if "val_idx" in arrays:
        vi = np.asarray(arrays["val_idx"])
        for p in range(out_vals.shape[0]):
            k = int(cnt[p])
            flat[vi[p, :k]] = out_vals[p, :k]
        return flat
    for p in range(out_vals.shape[0]):
        lo = int(vals_bounds[p, 0])
        flat[lo: lo + cnt[p]] = out_vals[p, : cnt[p]]
    return flat


def spmv_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Build the shard_map SpMV for a rows-lowered kernel. Returns a
    callable () -> y executing on ``mesh``."""
    B = kernel.shards[kernel.stmt.rhs.accesses()[0].tensor.name]
    c = kernel.shards[kernel.stmt.rhs.accesses()[1].tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    a = B.arrays
    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, vals, cvec):
            # leading shard axis has local extent 1 inside shard_map
            y = K.leaf_spmv_rows(pos[0], crd[0], vals[0], cvec)
            return y[None]
        return run

    run, args = _spmd_runner(
        "spmv_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], c.arrays["vals"]), build)

    def call():
        y_blocks = run(*args)
        # assemble global output (disjoint row blocks)
        out = np.zeros(n, np.float32)
        rb = np.asarray(a["row_start"])
        cnt = np.asarray(a["row_count"])
        yb = np.asarray(y_blocks)
        for p in range(yb.shape[0]):
            out[rb[p]: rb[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def spmv_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Non-zero strategy under shard_map: every shard computes a partial
    over the FULL output range, reduced with psum — the explicit form of
    the paper's "communication to reduce into the output" (§II-D)."""
    B = kernel.shards[kernel.stmt.rhs.accesses()[0].tensor.name]
    c = kernel.shards[kernel.stmt.rhs.accesses()[1].tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    a = B.arrays

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P())
        def run(rows, cols, vals, cvec):
            y = K.leaf_spmv_nnz(rows[0], cols[0], vals[0], cvec, n)
            return jax.lax.psum(y, axis_name=axis)
        return run

    run, args = _spmd_runner(
        "spmv_nnz", mesh, axis, (n,), specs,
        (a["dim0"], a["dim1"], a["vals"], c.arrays["vals"]), build)

    def call():
        return np.asarray(run(*args))

    call.placed = args
    return call


def spmm_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Row-based SpMM: each shard computes its row block against the
    replicated dense matrix (paper's SpMM algorithm, §VI-A)."""
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, vals, Cm):
            return K.leaf_spmm_rows(pos[0], crd[0], vals[0], Cm)[None]
        return run

    run, args = _spmd_runner(
        "spmm_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], C.arrays["vals"]), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def sddmm_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Non-zero based SDDMM: equal-nnz COO shards, dense factors
    replicated; outputs stay position-aligned (no reduction needed — the
    output pattern equals the input pattern, paper §V-B)."""
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    a = B.arrays

    specs = (P(axis), P(axis), P(axis), P(), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(rows, cols, vals, Cm, Dm):
            return K.leaf_sddmm_nnz(rows[0], cols[0], vals[0], Cm, Dm)[None]
        return run

    run, args = _spmd_runner(
        "sddmm_nnz", mesh, axis, (), specs,
        (a["dim0"], a["dim1"], a["vals"], C.arrays["vals"],
         D.arrays["vals"]), build)

    def call():
        out_vals = np.asarray(run(*args))
        Bt = accs[0].tensor
        return _assemble_vals(Bt.nnz, out_vals, a,
                              kernel.plans[Bt.name].vals_bounds)

    call.placed = args
    return call


def spmm_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Non-zero SpMM under shard_map: full-extent partials + psum. Uses
    GLOBAL row ids, so it is format-general — CSC's column-ordered position
    space works unchanged (no row-window locality to exploit)."""
    from .planner import sparse_pspecs
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    a = B.arrays
    sp = sparse_pspecs({"B": B, "C": C}, axis)
    specs = (sp["B"]["dim0"], sp["B"]["dim1"], sp["B"]["vals"],
             sp["C"]["vals"])

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P())
        def run(rows, cols, vals, Cm):
            y = K.leaf_spmm_nnz(rows[0], cols[0], vals[0], Cm, n)
            return jax.lax.psum(y, axis_name=axis)
        return run

    run, args = _spmd_runner(
        "spmm_nnz", mesh, axis, (n,), specs,
        (a["dim0"], a["dim1"], a["vals"], C.arrays["vals"]), build)

    def call():
        return np.asarray(run(*args))

    call.placed = args
    return call


def sddmm_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Row-based SDDMM under shard_map: B row shard (CSR convention — any
    row-partitionable format materializes to it) + C row block local, D
    replicated; per-shard output vals assembled by value-space bounds."""
    from .planner import sparse_pspecs
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    sp = sparse_pspecs({"B": B, "C": C, "D": D}, axis)
    specs = (sp["B"]["pos1"], sp["B"]["crd1"], sp["B"]["vals"],
             sp["C"]["vals"], sp["D"]["vals"])

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, vals, Cl, Dm):
            return K.leaf_sddmm_rows(pos[0], crd[0], vals[0], Cl[0],
                                     Dm)[None]
        return run

    run, args = _spmd_runner(
        "sddmm_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], C.arrays["vals"],
         D.arrays["vals"]), build)

    def call():
        out_vals = np.asarray(run(*args))
        return _assemble_vals(Bt.nnz, out_vals, a,
                              kernel.plans[Bt.name].vals_bounds)

    call.placed = args
    return call


def bcsr_spmv_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Direct blocked SpMV under shard_map: each color's shard carries
    (br, bc) value tiles over its block-row window; the dense vector is
    broadcast pre-packed into column blocks. Disjoint block-aligned row
    windows assemble without reduction."""
    B = kernel.shards[kernel.stmt.rhs.accesses()[0].tensor.name]
    c = kernel.shards[kernel.stmt.rhs.accesses()[1].tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    a = B.arrays
    c_blk = pack_vec_blocks(np.asarray(c.arrays["vals"]),
                            int(B.meta["grid_cols"]), int(B.meta["bc"]))

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, tiles, cb):
            return K.leaf_bcsr_spmv_rows(pos[0], crd[0], tiles[0], cb)[None]
        return run

    run, args = _spmd_runner(
        "bcsr_spmv_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], c_blk), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros(n, np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def bcsr_spmv_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Blocked non-zero SpMV under shard_map: every color reduces a
    full-block-grid partial with psum — global block-rows, so overlapping
    block-row ownership needs no window bookkeeping."""
    B = kernel.shards[kernel.stmt.rhs.accesses()[0].tensor.name]
    c = kernel.shards[kernel.stmt.rhs.accesses()[1].tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    gr = int(B.meta["grid_rows"])
    a = B.arrays
    c_blk = pack_vec_blocks(np.asarray(c.arrays["vals"]),
                            int(B.meta["grid_cols"]), int(B.meta["bc"]))

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P())
        def run(bd0, bd1, tiles, cb):
            y = K.leaf_bcsr_spmv_nnz(bd0[0], bd1[0], tiles[0], cb, gr)
            return jax.lax.psum(y, axis_name=axis)
        return run

    run, args = _spmd_runner(
        "bcsr_spmv_nnz", mesh, axis, (gr,), specs,
        (a["bdim0"], a["bdim1"], a["vals"], c_blk), build)

    def call():
        return np.asarray(run(*args))[:n]

    call.placed = args
    return call


def bcsr_spmm_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Blocked row-based SpMM: per color the shard's tiles contract against
    the broadcast row-blocked dense operand — every stored block a dense
    (br, bc) @ (bc, J) matmul."""
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                int(B.meta["grid_cols"]), int(B.meta["bc"]))

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, tiles, Cb):
            return K.leaf_bcsr_spmm_rows(pos[0], crd[0], tiles[0], Cb)[None]
        return run

    run, args = _spmd_runner(
        "bcsr_spmm_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], C_blk), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def bcsr_spmm_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Blocked non-zero SpMM under shard_map: global block-rows over the
    full grid extent, psum-reduced — the blocked analog of spmm_nnz."""
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    gr = int(B.meta["grid_rows"])
    a = B.arrays
    C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                int(B.meta["grid_cols"]), int(B.meta["bc"]))

    specs = (P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P())
        def run(bd0, bd1, tiles, Cb):
            y = K.leaf_bcsr_spmm_nnz(bd0[0], bd1[0], tiles[0], Cb, gr)
            return jax.lax.psum(y, axis_name=axis)
        return run

    run, args = _spmd_runner(
        "bcsr_spmm_nnz", mesh, axis, (gr,), specs,
        (a["bdim0"], a["bdim1"], a["vals"], C_blk), build)

    def call():
        return np.asarray(run(*args))[:n]

    call.placed = args
    return call


def bcsr_sddmm_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Blocked row-based SDDMM under shard_map: B's block-row shard sampled
    against its local C row blocks (block-aligned windows) and the
    broadcast column-blocked D; tiles reassemble by value-space bounds."""
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    br, bc = int(B.meta["br"]), int(B.meta["bc"])
    max_brows = int(B.meta["max_brows"])
    C_blk = pack_rowwindow_blocks(C.arrays["vals"], max_brows, br)
    D_blk = pack_mat_inner_blocks(np.asarray(D.arrays["vals"]),
                                  int(B.meta["grid_cols"]), bc)

    specs = (P(axis), P(axis), P(axis), P(axis), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(pos, crd, tiles, Cl, Db):
            brow = K.rows_from_pos(pos[0], crd[0].shape[0])
            return K.leaf_bcsr_sddmm(brow, crd[0], tiles[0], Cl[0],
                                     Db)[None]
        return run

    run, args = _spmd_runner(
        "bcsr_sddmm_rows", mesh, axis, (), specs,
        (a["pos1"], a["crd1"], a["vals"], C_blk, D_blk), build)

    def call():
        out_tiles = np.asarray(run(*args))
        total_blocks = int(Bt.levels[1].nnz or 0)
        return _assemble_vals(total_blocks, out_tiles, a,
                              kernel.plans[Bt.name].vals_bounds)

    call.placed = args
    return call


def bcsr_sddmm_nnz_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """Blocked non-zero SDDMM: equal stored-block shards sample the
    broadcast block-packed factors; output tiles stay aligned with the
    stored block positions (no reduction — pattern-preserving)."""
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    br, bc = int(B.meta["br"]), int(B.meta["bc"])
    C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                int(B.meta["grid_rows"]), br)
    D_blk = pack_mat_inner_blocks(np.asarray(D.arrays["vals"]),
                                  int(B.meta["grid_cols"]), bc)

    specs = (P(axis), P(axis), P(axis), P(), P())

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(axis))
        def run(bd0, bd1, tiles, Cb, Db):
            return K.leaf_bcsr_sddmm(bd0[0], bd1[0], tiles[0], Cb, Db)[None]
        return run

    run, args = _spmd_runner(
        "bcsr_sddmm_nnz", mesh, axis, (), specs,
        (a["bdim0"], a["bdim1"], a["vals"], C_blk, D_blk), build)

    def call():
        out_tiles = np.asarray(run(*args))
        total_blocks = int(Bt.levels[1].nnz or 0)
        return _assemble_vals(total_blocks, out_tiles, a,
                              kernel.plans[Bt.name].vals_bounds)

    call.placed = args
    return call


# ---------------------------------------------------------------------------
# 2-D grid builders — the SUMMA-style executors over a genuine
# Mesh((P, Q), ("x", "y")). The flat-color shard arrays reshape to
# (P, Q, ...) and shard over both axes; the dense co-operand windows shard
# over ONE axis (broadcast along the other falls out of the spec), and the
# contraction reduction is a psum scoped to the y axis only.
# ---------------------------------------------------------------------------

def _grid_axes(mesh: Mesh) -> tuple:
    if len(mesh.axis_names) != 2:
        raise ValueError(f"grid executor needs a 2-D mesh, got "
                         f"{mesh.axis_names}")
    return mesh.axis_names[0], mesh.axis_names[1]


def _grid_axes3(mesh: Mesh) -> tuple:
    if len(mesh.axis_names) != 3:
        raise ValueError(f"3-D grid executor needs a 3-D mesh, got "
                         f"{mesh.axis_names}")
    return mesh.axis_names[0], mesh.axis_names[1], mesh.axis_names[2]


def _grid_reshape(a: np.ndarray, P: int, Q: int) -> np.ndarray:
    return np.asarray(a).reshape((P, Q) + a.shape[1:])


def spmm_grid_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """2-D SpMM: tile (p, q) multiplies its B tile against C's q-th
    k-window (broadcast along x by the in_spec) and the grid row psums its
    partials along y ONLY — the SUMMA reduction."""
    ax, ay = _grid_axes(mesh)
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    Cw = C.arrays["vals"]                       # (Q, max_kw, J)
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax))
        def run(pos, crd, vals, Cw):
            y = K.leaf_spmm_rows(pos[0, 0], crd[0, 0], vals[0, 0], Cw[0])
            return jax.lax.psum(y, axis_name=ay)[None]
        return run

    run, args = _spmd_runner("spmm_grid_rows", mesh, (ax, ay), (), specs,
                             (pos, crd, vals, Cw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def spmv_grid_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    ax, ay = _grid_axes(mesh)
    B = kernel.shards[kernel.stmt.rhs.accesses()[0].tensor.name]
    c = kernel.shards[kernel.stmt.rhs.accesses()[1].tensor.name]
    n = kernel.stmt.lhs.tensor.shape[0]
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    cw = c.arrays["vals"]                       # (Q, max_kw)
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax))
        def run(pos, crd, vals, cw):
            y = K.leaf_spmv_rows(pos[0, 0], crd[0, 0], vals[0, 0], cw[0])
            return jax.lax.psum(y, axis_name=ay)[None]
        return run

    run, args = _spmd_runner("spmv_grid_rows", mesh, (ax, ay), (), specs,
                             (pos, crd, vals, cw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros(n, np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def sddmm_grid_rows_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """2-D SDDMM: owner-computes tiles — C row windows shard along x, D
    column windows along y, outputs stay tile-aligned (NO psum on either
    axis); host assembly scatters by the tiles' global value positions."""
    ax, ay = _grid_axes(mesh)
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    Cw = C.arrays["vals"]                       # (P, max_rw, K)
    Dw = D.arrays["vals"]                       # (Q, K, max_mw)
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ax), P(ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax, ay))
        def run(pos, crd, vals, Cw, Dw):
            out = K.leaf_sddmm_rows(pos[0, 0], crd[0, 0], vals[0, 0],
                                    Cw[0], Dw[0])
            return out[None, None]
        return run

    run, args = _spmd_runner("sddmm_grid_rows", mesh, (ax, ay), (), specs,
                             (pos, crd, vals, Cw, Dw), build)

    def call():
        out_vals = np.asarray(run(*args))       # (P, Q, max_tnnz)
        flat = np.zeros(Bt.nnz, np.float32)
        vi = np.asarray(a["val_idx"]).reshape(P_, Q_, -1)
        cnt = np.asarray(a["nnz_count"]).reshape(P_, Q_)
        for p in range(P_):
            for q in range(Q_):
                k = int(cnt[p, q])
                flat[vi[p, q, :k]] = out_vals[p, q, :k]
        return flat

    call.placed = args
    return call


def bcsr_spmm_grid_rows_spmd(kernel: LoweredKernel, mesh: Mesh,
                             axis: str = "x"):
    """Blocked 2-D SpMM: (br, bc) tile matmuls against the q-th window of
    the block-packed dense operand, psum along y."""
    ax, ay = _grid_axes(mesh)
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    from ..core.grid import pack_window_mat_row_blocks
    Cw = pack_window_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                    int(a["bcol_count"].max()),
                                    int(B.meta["bc"]))
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax))
        def run(pos, crd, tiles, Cw):
            y = K.leaf_bcsr_spmm_rows(pos[0, 0], crd[0, 0], tiles[0, 0],
                                      Cw[0])
            return jax.lax.psum(y, axis_name=ay)[None]
        return run

    run, args = _spmd_runner("bcsr_spmm_grid_rows", mesh, (ax, ay), (),
                             specs, (pos, crd, vals, Cw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


def spmm_grid_rep_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """2.5-D replicated SpMM over Mesh((P, Q, R)): B's (P, Q) tiles shard
    over (x, y) and the in_spec's silence on z replicates them across the
    z-layers; C's (Q, R) dense grid shards over (y, z). Each z-layer runs
    the SUMMA for its own output-column slab, so the psum is scoped to y
    ONLY — the (QR−1)-hop all-reduce of an unreplicated 3-D spread shrinks
    to Q−1 hops, which is exactly what the z-axis broadcast bought."""
    ax, ay, az = _grid_axes3(mesh)
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    Cw = C.arrays["vals"]                       # (Q, R, max_kw, max_jw)
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ay, az))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax, az))
        def run(pos, crd, vals, Cw):
            y = K.leaf_spmm_rows(pos[0, 0], crd[0, 0], vals[0, 0], Cw[0, 0])
            return jax.lax.psum(y, axis_name=ay)[None, None]
        return run

    run, args = _spmd_runner("spmm_grid_rep_rows", mesh, (ax, ay, az), (),
                             specs, (pos, crd, vals, Cw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        cs = np.asarray(C.arrays["col_start"])
        cw = np.asarray(C.arrays["col_count"])
        for p in range(yb.shape[0]):
            for r in range(yb.shape[1]):
                out[rs[p]: rs[p] + cnt[p], cs[r]: cs[r] + cw[r]] = \
                    yb[p, r, : cnt[p], : cw[r]]
        return out

    call.placed = args
    return call


def sddmm_grid_rep_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """2.5-D replicated SDDMM: B's sampling tiles shard over (x, y) and
    replicate across z; the contraction variable k splits over z — C's
    (P, R) grid shards over (x, z), D's (R, Q) grid over (z, y). Each
    z-layer samples a partial dot product and the psum is scoped to z
    ONLY (the single reduction axis); outputs stay tile-aligned."""
    ax, ay, az = _grid_axes3(mesh)
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    Cw = C.arrays["vals"]                       # (P, R, max_rw, max_kw)
    Dw = D.arrays["vals"]                       # (R, Q, max_kw, max_mw)
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ax, az), P(az, ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax, ay))
        def run(pos, crd, vals, Cw, Dw):
            out = K.leaf_sddmm_rows(pos[0, 0], crd[0, 0], vals[0, 0],
                                    Cw[0, 0], Dw[0, 0])
            return jax.lax.psum(out, axis_name=az)[None, None]
        return run

    run, args = _spmd_runner("sddmm_grid_rep_rows", mesh, (ax, ay, az), (),
                             specs, (pos, crd, vals, Cw, Dw), build)

    def call():
        out_vals = np.asarray(run(*args))       # (P, Q, max_tnnz)
        flat = np.zeros(Bt.nnz, np.float32)
        vi = np.asarray(a["val_idx"]).reshape(P_, Q_, -1)
        cnt = np.asarray(a["nnz_count"]).reshape(P_, Q_)
        for p in range(P_):
            for q in range(Q_):
                k = int(cnt[p, q])
                flat[vi[p, q, :k]] = out_vals[p, q, :k]
        return flat

    call.placed = args
    return call


def spmttkrp_grid3_spmd(kernel: LoweredKernel, mesh: Mesh, axis: str = "x"):
    """P×Q×R brick SpMTTKRP over Mesh((P, Q, R)): the COO brick arrays
    shard over all three axes, C's row windows over y, D's over z; each
    brick segment-sums its contraction and the partials psum over (y, z)
    — the Q·R bricks sharing a row window — landing row-aligned on x."""
    ax, ay, az = _grid_axes3(mesh)
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    D = kernel.shards[accs[2].tensor.name]
    out_shape = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    P_, Q_, R_ = int(B.meta["P"]), int(B.meta["Q"]), int(B.meta["R"])
    max_rows = int(B.meta["max_rows"])

    def brick(x):
        return np.asarray(x).reshape((P_, Q_, R_) + x.shape[1:])

    d0, d1, d2 = brick(a["dim0"]), brick(a["dim1"]), brick(a["dim2"])
    vals = brick(a["vals"])
    Cw = C.arrays["vals"]                       # (Q, max_jw, L)
    Dw = D.arrays["vals"]                       # (R, max_kw, L)
    specs = (P(ax, ay, az), P(ax, ay, az), P(ax, ay, az), P(ax, ay, az),
             P(ay), P(az))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax))
        def run(d0, d1, d2, vals, Cw, Dw):
            y = K.leaf_spmttkrp_nnz(d0[0, 0, 0], d1[0, 0, 0], d2[0, 0, 0],
                                    vals[0, 0, 0], Cw[0], Dw[0], max_rows)
            return jax.lax.psum(y, axis_name=(ay, az))[None]
        return run

    run, args = _spmd_runner("spmttkrp_grid3_rows", mesh, (ax, ay, az), (),
                             specs, (d0, d1, d2, vals, Cw, Dw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros(out_shape, np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


SPMD_BUILDERS: Dict[str, Callable] = {
    "spmv_rows": spmv_rows_spmd,
    "spmv_nnz": spmv_nnz_spmd,
    "spmm_rows": spmm_rows_spmd,
    "spmm_nnz": spmm_nnz_spmd,
    "sddmm_rows": sddmm_rows_spmd,
    "sddmm_nnz": sddmm_nnz_spmd,
    "bcsr_spmv_rows": bcsr_spmv_rows_spmd,
    "bcsr_spmv_nnz": bcsr_spmv_nnz_spmd,
    "bcsr_spmm_rows": bcsr_spmm_rows_spmd,
    "bcsr_spmm_nnz": bcsr_spmm_nnz_spmd,
    "bcsr_sddmm_rows": bcsr_sddmm_rows_spmd,
    "bcsr_sddmm_nnz": bcsr_sddmm_nnz_spmd,
    "spmv_grid_rows": spmv_grid_rows_spmd,
    "spmm_grid_rows": spmm_grid_rows_spmd,
    "sddmm_grid_rows": sddmm_grid_rows_spmd,
    "bcsr_spmm_grid_rows": bcsr_spmm_grid_rows_spmd,
    "spmm_grid_rep_rows": spmm_grid_rep_spmd,
    "sddmm_grid_rep_rows": sddmm_grid_rep_spmd,
    "spmttkrp_grid3_rows": spmttkrp_grid3_spmd,
}


def to_spmd(kernel: LoweredKernel, mesh: Mesh = None, axis: str = "x",
            overlap: bool = False, overlap_chunks: int = 2):
    """SPMD executor for a lowered kernel, when a builder exists.

    The returned ``() -> output`` callable runs on shard arrays placed once,
    here, under the builder's in_specs; ``call.placed`` holds them (what
    each device stores is ``placed[i].addressable_shards``).

    ``mesh`` is data, not trace state: pass nothing to realize the
    kernel's own Machine, a ``jax.sharding.Mesh``, or a ``Machine``
    directly (realized here) — the elastic path hands the resized Machine
    straight through after ``relower``.

    Grid (multi-axis) NON-ZERO kernels reuse their 1-D builders with the
    flat color axis sharded over BOTH mesh axes and the reduction psum
    scoped to both — the nested pos-split is the flat P*Q split.

    ``overlap=True`` selects the comm/compute-overlapped builder variant
    where one exists (grid SpMM): the dense co-operand is consumed in
    ``overlap_chunks`` column chunks whose SUMMA psums have no data
    dependence on the following chunk's leaf, so the compiled program can
    run chunk t's reduction while chunk t+1's leaf computes — bit-for-bit
    equal to the unchunked builder (column chunking never reorders any
    per-element reduction)."""
    if mesh is None:
        mesh = machine_to_mesh(kernel.machine)
    elif isinstance(mesh, Machine):
        mesh = machine_to_mesh(mesh)
    strat = kernel.strategy
    if getattr(strat, "is_grid", False) and strat.space == "nnz" \
            and len(mesh.axis_names) >= 2:
        axis = tuple(mesh.axis_names)
    if overlap:
        builder = OVERLAP_SPMD_BUILDERS.get(kernel.leaf_name)
        if builder is None:
            raise NotImplementedError(
                f"no overlapped shard_map builder for leaf "
                f"{kernel.leaf_name}; supported: "
                f"{sorted(OVERLAP_SPMD_BUILDERS)}")
        with telemetry.span("execute.spmd.build", leaf=kernel.leaf_name,
                            overlap=True, chunks=overlap_chunks):
            call = builder(kernel, mesh, axis=axis, chunks=overlap_chunks)
    else:
        builder = SPMD_BUILDERS.get(kernel.leaf_name)
        if builder is None:
            raise NotImplementedError(
                f"no shard_map builder for leaf {kernel.leaf_name}; "
                "the vmap simulation backend covers it")
        with telemetry.span("execute.spmd.build", leaf=kernel.leaf_name):
            call = builder(kernel, mesh, axis=axis)
    return _run_span(call, kernel.leaf_name)


def _run_span(call, leaf: str):
    """The shard_map ``call`` under the ``run`` span (``spmd=True``), as
    ``LoweredKernel.run`` is on the one-chip path; ``placed`` is kept."""

    def run():
        if not telemetry.TRACER.enabled:
            return call()
        with telemetry.span("run", leaf=leaf, spmd=True):
            return call()

    run.placed = call.placed
    return run


# ---------------------------------------------------------------------------
# Per-piece leaf profiling (telemetry, ISSUE 9): run each color's leaf
# kernel ALONE and wall-time it through block_until_ready. The emitters
# vmap all pieces into one launch, so a straggler piece is invisible in
# aggregate wall time; the per-piece profile is the skew histogram whose
# flags feed the existing lower(weights=) straggler re-plan path.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PieceProfile:
    """Per-piece leaf wall times for one lowered kernel."""

    leaf_name: str
    seconds: np.ndarray               # (pieces,) best-of-iters per piece

    def skew(self) -> float:
        """max/mean piece time — 1.0 is perfectly balanced."""
        m = float(self.seconds.mean())
        return float(self.seconds.max()) / m if m > 0 else 1.0

    def stragglers(self, threshold: float = 1.5):
        """Piece ids slower than ``threshold``× the mean."""
        m = float(self.seconds.mean())
        if m <= 0:
            return []
        return [int(p) for p in np.nonzero(self.seconds > threshold * m)[0]]

    def replan_weights(self) -> np.ndarray:
        """Mean-normalized inverse-time weights for ``lower(weights=)`` /
        ``relower(weights=)`` — a faster piece gets proportionally more
        non-zeros, the same convention as StragglerMitigator.weights."""
        inv = 1.0 / np.maximum(self.seconds, 1e-12)
        return inv / inv.mean()

    def as_dict(self):
        return {"leaf": self.leaf_name,
                "seconds": [float(s) for s in self.seconds],
                "skew": self.skew()}


def _sparse_and_dense(kernel):
    accs = kernel.stmt.rhs.accesses()
    B = kernel.shards[accs[0].tensor.name]
    C = kernel.shards[accs[1].tensor.name]
    return B, C


def _pieces_spmv_rows(kernel):
    B, c = _sparse_and_dense(kernel)
    a = B.arrays
    cv = jnp.asarray(c.arrays["vals"])
    pos, crd, vals = (jnp.asarray(a["pos1"]), jnp.asarray(a["crd1"]),
                      jnp.asarray(a["vals"]))
    return K.leaf_spmv_rows, [(pos[p], crd[p], vals[p], cv)
                              for p in range(pos.shape[0])]


def _pieces_spmm_rows(kernel):
    B, C = _sparse_and_dense(kernel)
    a = B.arrays
    Cv = jnp.asarray(C.arrays["vals"])
    pos, crd, vals = (jnp.asarray(a["pos1"]), jnp.asarray(a["crd1"]),
                      jnp.asarray(a["vals"]))
    return K.leaf_spmm_rows, [(pos[p], crd[p], vals[p], Cv)
                              for p in range(pos.shape[0])]


def _pieces_spmv_nnz(kernel):
    from ..core.lower import _nnz_row_windows
    B, c = _sparse_and_dense(kernel)
    n = kernel.stmt.lhs.tensor.shape[0]
    row_start, _, max_rows = _nnz_row_windows(B, n)
    a = B.arrays
    rl = jnp.clip(jnp.asarray(a["dim0"])
                  - jnp.asarray(row_start)[:, None], 0, max_rows - 1)
    cols, vals = jnp.asarray(a["dim1"]), jnp.asarray(a["vals"])
    cv = jnp.asarray(c.arrays["vals"])

    def leaf(r, cc, v, cvec):
        return K.leaf_spmv_nnz(r, cc, v, cvec, max_rows)

    return leaf, [(rl[p], cols[p], vals[p], cv)
                  for p in range(rl.shape[0])]


def _pieces_spmm_nnz(kernel):
    from ..core.lower import _nnz_row_windows
    B, C = _sparse_and_dense(kernel)
    row_start, _, max_rows = _nnz_row_windows(
        B, kernel.stmt.lhs.tensor.shape[0])
    a = B.arrays
    rl = jnp.clip(jnp.asarray(a["dim0"])
                  - jnp.asarray(row_start)[:, None], 0, max_rows - 1)
    cols, vals = jnp.asarray(a["dim1"]), jnp.asarray(a["vals"])
    Cv = jnp.asarray(C.arrays["vals"])

    def leaf(r, cc, v, Cm):
        return K.leaf_spmm_nnz(r, cc, v, Cm, max_rows)

    return leaf, [(rl[p], cols[p], vals[p], Cv)
                  for p in range(rl.shape[0])]


def _pieces_spmv_grid_rows(kernel):
    B, c = _sparse_and_dense(kernel)
    a = B.arrays
    Q = int(B.meta["Q"])
    cw = jnp.asarray(c.arrays["vals"])          # (Q, max_kw)
    pos, crd, vals = (jnp.asarray(a["pos1"]), jnp.asarray(a["crd1"]),
                      jnp.asarray(a["vals"]))
    return K.leaf_spmv_rows, [(pos[p], crd[p], vals[p], cw[p % Q])
                              for p in range(pos.shape[0])]


def _pieces_spmm_grid_rows(kernel):
    B, C = _sparse_and_dense(kernel)
    a = B.arrays
    Q = int(B.meta["Q"])
    Cw = jnp.asarray(C.arrays["vals"])          # (Q, max_kw, J)
    pos, crd, vals = (jnp.asarray(a["pos1"]), jnp.asarray(a["crd1"]),
                      jnp.asarray(a["vals"]))
    return K.leaf_spmm_rows, [(pos[p], crd[p], vals[p], Cw[p % Q])
                              for p in range(pos.shape[0])]


#: leaf name -> (kernel) -> (leaf_fn, [per-piece arg tuples]). Every
#: piece's args share shapes, so the jitted leaf compiles once.
PIECE_PROFILERS: Dict[str, Callable] = {
    "spmv_rows": _pieces_spmv_rows,
    "spmm_rows": _pieces_spmm_rows,
    "spmv_nnz": _pieces_spmv_nnz,
    "spmm_nnz": _pieces_spmm_nnz,
    "spmv_grid_rows": _pieces_spmv_grid_rows,
    "spmm_grid_rows": _pieces_spmm_grid_rows,
}


def profile_pieces(kernel: LoweredKernel, iters: int = 3,
                   warmup: int = 1) -> PieceProfile:
    """Wall-time every piece's leaf kernel individually (best of
    ``iters`` after ``warmup``, synchronized with block_until_ready).

    Records one ``execute.piece`` span + an ``executor.piece_seconds``
    histogram observation per piece, and the profile's skew as the
    ``executor.piece_skew`` gauge — the telemetry surface the serving
    path's straggler re-plans read."""
    slicer = PIECE_PROFILERS.get(kernel.leaf_name)
    if slicer is None:
        raise NotImplementedError(
            f"no per-piece profiler for leaf {kernel.leaf_name}; "
            f"supported: {sorted(PIECE_PROFILERS)}")
    leaf, piece_args = slicer(kernel)
    jleaf = jax.jit(leaf)
    n = len(piece_args)
    secs = np.full(n, np.inf)
    for args in piece_args:                      # compile + warm every shape
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(jleaf(*args))
    for _ in range(max(iters, 1)):
        for p, args in enumerate(piece_args):
            with telemetry.span("execute.piece", piece=p,
                                leaf=kernel.leaf_name) as sp:
                t0 = time.perf_counter()
                jax.block_until_ready(jleaf(*args))
                dt = time.perf_counter() - t0
                sp.set(seconds=dt)
            secs[p] = min(secs[p], dt)
    for s in secs:
        telemetry.METRICS.observe("executor.piece_seconds", float(s))
    prof = PieceProfile(leaf_name=kernel.leaf_name, seconds=secs)
    telemetry.METRICS.gauge("executor.piece_skew", prof.skew())
    return prof


# -- Comm/compute overlap ---------------------------------------------------
#
# The serving fast path's second layer: double-buffered shard transfers.
# The dense co-operand of an SpMM is consumed in column chunks; while the
# leaf kernel contracts chunk t-1, chunk t's shard transfer is already in
# flight (collectives.prefetch dispatches jax.device_put asynchronously).
# Column chunking is bit-for-bit exact — every output element's k-reduction
# runs in the same order as the unchunked kernel; chunks are independent
# output-column lanes concatenated at the end.

#: Leaves whose dense operand flows straight into the jitted runner as a
#: device array. The bcsr paths re-pack on the host (pack_mat_row_blocks
#: over np.asarray), which would force the transferred chunk back through
#: host memory and defeat the double buffering.
_OVERLAP_LEAVES = ("spmm_rows", "spmm_nnz", "spmm_grid_rows")


def _chunk_bounds(J: int, chunks: int):
    """Equal-width column chunks (last takes the remainder) — at most two
    distinct widths, so the runner caches hold at most two entries per
    leaf regardless of chunk count."""
    chunks = max(1, min(int(chunks), int(J)))
    cw = -(-int(J) // chunks)
    return [(s, min(int(J), s + cw)) for s in range(0, int(J), cw)]


def run_overlapped(kernel: LoweredKernel, chunks: int = 2,
                   overlap: bool = True) -> np.ndarray:
    """Execute an SpMM kernel with double-buffered dense-operand chunks.

    Pipelined loop: issue chunk t's shard transfer, compute chunk t-1's
    leaf (the transfer rides under it), block on the transfer, emit chunk
    t's runner against the landed device arrays. ``overlap=False`` runs
    the same chunking sequentially (issue, wait, compute) — the baseline
    the bench compares against; both orders return bit-for-bit identical
    results (and identical to ``kernel.run()``).

    Per-chunk attribution lands as ``execute.overlap.chunk`` instants
    (comm_s, hidden_s, bytes) under one ``execute.overlap`` span, rolled
    up by :func:`repro.runtime.telemetry.overlap_report`; byte totals are
    mirrored into ``kernel.comm.overlap_total_bytes`` /
    ``overlap_hidden_bytes`` (attribution only — never added to
    ``total_network_bytes``). ``hidden_s`` is the wall-clock window the
    transfer spent under the previous chunk's compute: the host cannot
    observe the exact landing instant without a callback, so the window
    is clamped to the measured issue→ready duration.
    """
    from ..core import grid as grid_mod
    from ..core import lower as lower_mod
    from ..core.tensor import Tensor
    from .collectives import prefetch, wait

    if kernel.leaf_name not in _OVERLAP_LEAVES:
        raise NotImplementedError(
            f"run_overlapped supports leaves {_OVERLAP_LEAVES}; got "
            f"{kernel.leaf_name} (bcsr paths re-pack on host)")
    stmt = kernel.stmt
    strat = kernel.strategy
    _, Cacc = stmt.rhs.accesses()
    cname = Cacc.tensor.name
    oname = stmt.lhs.tensor.name
    cplan = kernel.plans[cname]
    if not cplan.replicated and cplan.grid is None \
            and cplan.root_coord_bounds is None:
        raise NotImplementedError(
            "run_overlapped chunks the dense operand by columns; a "
            "column-partitioned operand's bounds would change per chunk")
    Cfull = np.asarray(cplan.tensor.to_dense(), np.float32)
    n, J = (int(d) for d in stmt.lhs.tensor.shape)
    bounds = _chunk_bounds(J, chunks)

    def prep(c0, c1):
        """Host-side pack of one chunk's shard (NOT the transfer)."""
        Ct = Tensor.from_dense(cname, Cfull[:, c0:c1])
        plan_t = dataclasses.replace(cplan, tensor=Ct)
        hs = lower_mod._materialize_dense_operand(
            Ct, plan_t, strat.pieces, cache=False)
        nb = int(sum(np.asarray(v).nbytes for v in hs.arrays.values()))
        return Ct, plan_t, hs, nb

    def build(c0, c1, Ct, plan_t, host_shard, dev_arrays):
        """Emit the chunk runner against the landed device arrays."""
        Ot = Tensor.from_dense(oname, np.zeros((n, c1 - c0), np.float32))
        cstmt = stmt.with_tensors({cname: Ct, oname: Ot})
        plans = dict(kernel.plans)
        plans[cname] = plan_t
        if oname in plans:
            plans[oname] = dataclasses.replace(plans[oname], tensor=Ot)
        shards = dict(kernel.shards)
        shards[cname] = dataclasses.replace(host_shard, arrays=dev_arrays)
        if getattr(strat, "is_grid", False) and strat.space == "universe":
            gp = grid_mod.compute_grid_plan(cstmt, strat)
            _, runner = grid_mod._emit_grid(cstmt, strat, gp, plans,
                                            shards, jit=True)
        else:
            _, runner = lower_mod._emit(cstmt, strat, plans, shards,
                                        jit=True)
        return runner

    results = [None] * len(bounds)
    total_comm = total_hidden = 0.0
    total_bytes = hidden_bytes = 0
    with telemetry.span("execute.overlap", leaf=kernel.leaf_name,
                        chunks=len(bounds), overlap=bool(overlap)) as osp:
        if not overlap or len(bounds) == 1:
            for t, (c0, c1) in enumerate(bounds):
                Ct, plan_t, hs, nb = prep(c0, c1)
                t0 = time.perf_counter()
                with telemetry.span("execute.overlap.xfer", chunk=t,
                                    bytes=nb):
                    dev = wait(prefetch(hs.arrays))
                comm = max(time.perf_counter() - t0, 1e-9)
                runner = build(c0, c1, Ct, plan_t, hs, dev)
                with telemetry.span("execute.overlap.compute", chunk=t):
                    results[t] = np.asarray(runner())
                telemetry.instant("execute.overlap.chunk", chunk=t,
                                  comm_s=comm, hidden_s=0.0, bytes=nb)
                total_comm += comm
                total_bytes += nb
        else:
            preps = [prep(c0, c1) for (c0, c1) in bounds]
            pending = None                # (chunk index, emitted runner)
            for t in range(len(bounds) + 1):
                inflight = None
                if t < len(bounds):
                    Ct, plan_t, hs, nb = preps[t]
                    t_issue = time.perf_counter()
                    with telemetry.span("execute.overlap.xfer", chunk=t,
                                        bytes=nb):
                        dev = prefetch(hs.arrays)      # async dispatch
                    inflight = (t, Ct, plan_t, hs, dev, t_issue, nb)
                t_comp_end = None
                if pending is not None:
                    pt, runner = pending
                    with telemetry.span("execute.overlap.compute",
                                        chunk=pt):
                        results[pt] = np.asarray(runner())
                    t_comp_end = time.perf_counter()
                    pending = None
                if inflight is not None:
                    ct, Ct, plan_t, hs, dev, t_issue, nb = inflight
                    dev = wait(dev)
                    t_ready = time.perf_counter()
                    comm = max(t_ready - t_issue, 1e-9)
                    hid = 0.0
                    if t_comp_end is not None:
                        hid = min(max(t_comp_end - t_issue, 0.0), comm)
                    telemetry.instant("execute.overlap.chunk", chunk=ct,
                                      comm_s=comm, hidden_s=hid, bytes=nb)
                    total_comm += comm
                    total_hidden += hid
                    total_bytes += nb
                    hidden_bytes += int(nb * (hid / comm))
                    c0, c1 = bounds[ct]
                    pending = (ct, build(c0, c1, Ct, plan_t, hs, dev))
        eff = (total_hidden / total_comm) if total_comm > 0 else 0.0
        osp.set(comm_s=total_comm, hidden_s=total_hidden, efficiency=eff)
    telemetry.METRICS.counter("executor.overlap.comm_seconds", total_comm)
    telemetry.METRICS.counter("executor.overlap.hidden_seconds",
                              total_hidden)
    telemetry.METRICS.counter("executor.overlap.bytes", float(total_bytes))
    telemetry.METRICS.counter("executor.overlap.hidden_bytes",
                              float(hidden_bytes))
    telemetry.METRICS.gauge("executor.overlap.efficiency", eff)
    kernel.comm.overlap_total_bytes += total_bytes
    kernel.comm.overlap_hidden_bytes += hidden_bytes
    return np.concatenate(results, axis=1)


def spmm_grid_rows_overlap_spmd(kernel: LoweredKernel, mesh: Mesh,
                                axis: str = "x", chunks: int = 2):
    """Overlapped 2-D SpMM: identical SUMMA to :func:`spmm_grid_rows_spmd`
    but the dense k-window is consumed in column chunks whose psums carry
    no data dependence on the next chunk's leaf — the compiled program is
    free to run chunk t's y-axis reduction while chunk t+1's local
    contraction executes. Bit-for-bit equal to the unchunked builder:
    column chunks are independent output lanes, and each lane's k-order
    psum tree is unchanged."""
    ax, ay = _grid_axes(mesh)
    Bacc, Cacc = kernel.stmt.rhs.accesses()
    B = kernel.shards[Bacc.tensor.name]
    C = kernel.shards[Cacc.tensor.name]
    n, J = kernel.stmt.lhs.tensor.shape
    a = B.arrays
    P_, Q_ = int(B.meta["P"]), int(B.meta["Q"])
    pos = _grid_reshape(a["pos1"], P_, Q_)
    crd = _grid_reshape(a["crd1"], P_, Q_)
    vals = _grid_reshape(a["vals"], P_, Q_)
    Cw = C.arrays["vals"]                       # (Q, max_kw, J)
    bounds = tuple(_chunk_bounds(int(J), chunks))
    specs = (P(ax, ay), P(ax, ay), P(ax, ay), P(ay))

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=P(ax))
        def run(pos, crd, vals, Cw):
            outs = []
            for c0, c1 in bounds:
                y = K.leaf_spmm_rows(pos[0, 0], crd[0, 0], vals[0, 0],
                                     Cw[0][:, c0:c1])
                outs.append(jax.lax.psum(y, axis_name=ay))
            return jnp.concatenate(outs, axis=-1)[None]
        return run

    run, args = _spmd_runner("spmm_grid_rows_overlap", mesh, (ax, ay),
                             (bounds,), specs, (pos, crd, vals, Cw), build)

    def call():
        yb = np.asarray(run(*args))
        out = np.zeros((n, J), np.float32)
        rs, cnt = np.asarray(a["row_start"]), np.asarray(a["row_count"])
        for p in range(yb.shape[0]):
            out[rs[p]: rs[p] + cnt[p]] = yb[p, : cnt[p]]
        return out

    call.placed = args
    return call


OVERLAP_SPMD_BUILDERS: Dict[str, Callable] = {
    "spmm_grid_rows": spmm_grid_rows_overlap_spmd,
}
