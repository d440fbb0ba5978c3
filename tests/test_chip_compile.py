"""The main path's leaf programs compile for a TPU v5e at smoke sizes.

Compiled ahead of time for a described (not attached) ``v5e:2x2``
topology, at the sizes ``chip_smoke.py`` runs: the chip's compiler
refuses what interpret mode and the CPU backend accept (unaligned
blocks, programs that overflow the 16 GB of HBM), and these tests catch
that without a chip. Each program must leave a quarter of the chip's
memory free, the margin ``chip_smoke.py`` sizes its phases by.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers each import
this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.lower import _scatter_rows, _scatter_vals
from repro.kernels import ref as K

HBM_LIMIT = 12e9          # 16 GB less a quarter
F32, I32 = jnp.float32, jnp.int32
PIECES = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_LIMIT, f"{total / 1e9:.2f} GB on one chip"
    return compiled


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_spmv_rows_leaf_compiles(one_chip):
    """SpMV under the row schedule: powerlaw n = 2^22, the largest row
    piece of the smoke's matrix holds ~15.4M of its ~47M non-zeros."""
    n, nnz_piece = 1 << 22, 15_400_000
    rows = n // PIECES

    def fn(pos, crd, vals, c, row_start, row_count):
        blocks = jax.vmap(K.leaf_spmv_rows, in_axes=(0, 0, 0, None))(
            pos, crd, vals, c)
        return _scatter_rows((n,), blocks, row_start, row_count)

    _compile(fn, _s((PIECES, rows + 1), I32, one_chip),
             _s((PIECES, nnz_piece), I32, one_chip),
             _s((PIECES, nnz_piece), F32, one_chip),
             _s((n,), F32, one_chip), _s((PIECES,), I32, one_chip),
             _s((PIECES,), I32, one_chip))


def test_spmm_nnz_leaf_compiles(one_chip):
    """SpMM under the non-zero schedule (an autoscheduler candidate):
    n = 2^19, ~7.7M non-zeros in equal quarters, J = 128."""
    n, J, nnz_piece = 1 << 19, 128, 1_920_000

    def fn(rows, cols, vals, C, row_start, row_count):
        rl = jnp.clip(rows - row_start[:, None], 0, n - 1)
        blocks = jax.vmap(K.leaf_spmm_nnz, in_axes=(0, 0, 0, None, None))(
            rl, cols, vals, C, n)
        return _scatter_rows((n, J), blocks, row_start, row_count)

    _compile(fn, _s((PIECES, nnz_piece), I32, one_chip),
             _s((PIECES, nnz_piece), I32, one_chip),
             _s((PIECES, nnz_piece), F32, one_chip),
             _s((n, J), F32, one_chip), _s((PIECES,), I32, one_chip),
             _s((PIECES,), I32, one_chip))


def test_sddmm_nnz_leaf_compiles(one_chip):
    """SDDMM under the non-zero schedule at the smoke's n = 2^18 (cut
    from 2^19, which overflows the margin), K = 64, up to 16 non-zeros
    per row in equal quarters."""
    n, kdim = 1 << 18, 64
    nnz_piece = 16 * n // PIECES

    def fn(rows, cols, vals, C, D, counts, nnz_start):
        out = jax.vmap(K.leaf_sddmm_nnz, in_axes=(0, 0, 0, None, None))(
            rows, cols, vals, C, D)
        return _scatter_vals(PIECES * nnz_piece, out, nnz_start, counts)

    _compile(fn, _s((PIECES, nnz_piece), I32, one_chip),
             _s((PIECES, nnz_piece), I32, one_chip),
             _s((PIECES, nnz_piece), F32, one_chip),
             _s((n, kdim), F32, one_chip), _s((kdim, n), F32, one_chip),
             _s((PIECES,), I32, one_chip), _s((PIECES,), I32, one_chip))


def test_bcsr_spmm_leaf_compiles(one_chip):
    """Blocked SpMM: banded n = 2^20, bandwidth 8, (8, 8) tiles — three
    stored blocks per block row — against J = 128 at HIGHEST precision."""
    n, J, br = 1 << 20, 128, 8
    grid = n // br
    blocks_piece = 3 * grid // PIECES + 8

    def fn(pos, crd, tiles, C_blk, row_start, row_count):
        out = jax.vmap(K.leaf_bcsr_spmm_rows, in_axes=(0, 0, 0, None))(
            pos, crd, tiles, C_blk)
        return _scatter_rows((n, J), out.reshape(PIECES, -1, J),
                             row_start, row_count)

    compiled = _compile(
        fn, _s((PIECES, grid // PIECES + 1), I32, one_chip),
        _s((PIECES, blocks_piece), I32, one_chip),
        _s((PIECES, blocks_piece, br, br), F32, one_chip),
        _s((grid, br, J), F32, one_chip), _s((PIECES,), I32, one_chip),
        _s((PIECES,), I32, one_chip))
    assert "convolution" in compiled.as_text() or "dot" in compiled.as_text()


def test_grid_spmm_shard_map_compiles(topo):
    """The 2x2 SUMMA SpMM of ``executor.spmm_grid_rows_spmd``: B tiles
    sharded over (x, y), C's k-windows over y, partials psum'd over y —
    on a mesh of the four described chips."""
    n, J, nnz_tile = 1 << 19, 128, 1_400_000
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("x", "y"),
                axis_types=(AxisType.Auto,) * 2)
    tile, kwin = NamedSharding(mesh, P("x", "y")), NamedSharding(mesh, P("y"))

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("x", "y"),) * 3 + (P("y"),),
                       out_specs=P("x"))
    def grid_spmm(pos, crd, vals, Cw):
        y = K.leaf_spmm_rows(pos[0, 0], crd[0, 0], vals[0, 0], Cw[0])
        return jax.lax.psum(y, axis_name="y")[None]

    compiled = _compile(grid_spmm, _s((2, 2, n // 2 + 1), I32, tile),
                        _s((2, 2, nnz_tile), I32, tile),
                        _s((2, 2, nnz_tile), F32, tile),
                        _s((2, n // 2, J), F32, kwin))
    assert "all-reduce" in compiled.as_text()
