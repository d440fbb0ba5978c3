"""Direct blocked (BCSR) execution path: block-shape sweep against the
interpreter oracle, Pallas blocked-kernel validation, blocked shard_map
builders, and the satellite fixes that rode along (spttv output format,
spadd3 nnz stream materialization)."""
import numpy as np
import pytest

import repro.core as rc
from repro.core import formats as F
from repro.core import partition as P
from repro.core.interp import interpret
from repro.core.lower import default_nnz_schedule, default_row_schedule, lower
from repro.core.tensor import Tensor

# (1,1) degenerate blocks, square, rectangular, and two shapes that do NOT
# divide the 19x13 operand — boundary blocks carry padding cells that must
# never leak into results.
BLOCK_SHAPES = [(1, 1), (2, 2), (4, 8), (3, 5)]
N, M, K = 19, 13, 5


def _operand(rng, empty=False):
    if empty:
        return np.zeros((N, M), np.float32)
    d = ((rng.random((N, M)) < 0.25) *
         rng.standard_normal((N, M))).astype(np.float32)
    d[rng.integers(0, N)] = 0                                    # empty row
    return d


def _stmt(expr, fm, rng, empty=False):
    dB = _operand(rng, empty)
    B = Tensor.from_dense("B", dB, fm)
    if expr == "spmv":
        c = Tensor.from_dense("c", rng.standard_normal(M).astype(np.float32))
        return rc.parse_tin("a(i) = B(i,j) * c(j)",
                            a=Tensor.zeros_dense("a", (N,)), B=B, c=c)
    if expr == "spmm":
        C = Tensor.from_dense(
            "C", rng.standard_normal((M, 7)).astype(np.float32))
        return rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                            A=Tensor.zeros_dense("A", (N, 7)), B=B, C=C)
    if expr == "sddmm":
        C = Tensor.from_dense(
            "C", rng.standard_normal((N, K)).astype(np.float32))
        D = Tensor.from_dense(
            "D", rng.standard_normal((K, M)).astype(np.float32))
        A = Tensor.from_dense("A", (dB != 0) * 1.0, F.CSR())
        return rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)",
                            A=A, B=B, C=C, D=D)
    d2 = _operand(rng, empty)
    d3 = _operand(rng, empty)
    return rc.parse_tin(
        "A(i,j) = B(i,j) + C(i,j) + D(i,j)",
        A=Tensor.from_dense("A", np.zeros((N, M), np.float32), F.CSR()),
        B=B, C=Tensor.from_dense("C", d2, fm),
        D=Tensor.from_dense("D", d3, fm))


@pytest.mark.parametrize("block", BLOCK_SHAPES,
                         ids=[f"{b[0]}x{b[1]}" for b in BLOCK_SHAPES])
@pytest.mark.parametrize("strategy", ["rows", "nnz"])
@pytest.mark.parametrize("expr", ["spmv", "spmm", "sddmm", "spadd3"])
def test_blocked_leaves_match_oracle(expr, strategy, block):
    """Property over the block-shape grid: every blocked cell lowers with
    NO conversion fallback and matches the interpreter oracle — including
    boundary blocks of the non-divisible shapes."""
    rng = np.random.default_rng(hash((expr, strategy, block)) % 2**31)
    stmt = _stmt(expr, F.BCSR(block), rng)
    machine = rc.Machine(("x", 3))       # non-divisible piece count
    sched = (default_row_schedule(stmt, machine) if strategy == "rows"
             else default_nnz_schedule(stmt, machine))
    k = lower(stmt, machine, schedule=sched)
    assert k.fallbacks == [], f"blocked cell fell back: {k.fallbacks}"
    assert k.leaf_name.startswith("bcsr_"), k.leaf_name
    res = k.run()
    got = res.to_dense() if isinstance(res, Tensor) else res
    np.testing.assert_allclose(got, interpret(stmt), atol=1e-3)


def test_blocked_empty_operands():
    rng = np.random.default_rng(0)
    for strategy in ("rows", "nnz"):
        stmt = _stmt("spadd3", F.BCSR((2, 2)), rng, empty=True)
        machine = rc.Machine(("x", 4))
        sched = (default_row_schedule(stmt, machine) if strategy == "rows"
                 else default_nnz_schedule(stmt, machine))
        k = lower(stmt, machine, schedule=sched)
        assert k.fallbacks == []
        np.testing.assert_allclose(k.run().to_dense(),
                                   np.zeros((N, M), np.float32))


def test_mixed_block_shapes_fall_back():
    """spadd3 with disagreeing block layouts cannot use the tile-union
    leaves — it must take the logged conversion, not miscompute."""
    rng = np.random.default_rng(1)
    B = Tensor.from_dense("B", _operand(rng), F.BCSR((2, 2)))
    C = Tensor.from_dense("C", _operand(rng), F.BCSR((3, 5)))
    D = Tensor.from_dense("D", _operand(rng), F.BCSR((2, 2)))
    stmt = rc.parse_tin(
        "A(i,j) = B(i,j) + C(i,j) + D(i,j)",
        A=Tensor.from_dense("A", np.zeros((N, M), np.float32), F.CSR()),
        B=B, C=C, D=D)
    machine = rc.Machine(("x", 2))
    k = lower(stmt, machine)
    assert len(k.fallbacks) == 3        # all blocked operands converted
    np.testing.assert_allclose(k.run().to_dense(), interpret(stmt),
                               atol=1e-3)


@pytest.mark.parametrize("shape,block", [((19, 13), (2, 2)),
                                         ((37, 53), (4, 8))])
def test_bcsr_pallas_kernels(shape, block):
    """Pallas blocked kernels (interpret mode) against the jnp leaves and
    the dense oracle."""
    from repro.kernels import ops
    rng = np.random.default_rng(hash(shape) % 2**31)
    n, m = shape
    dense = ((rng.random((n, m)) < 0.3) *
             rng.standard_normal((n, m))).astype(np.float32)
    t = Tensor.from_dense("B", dense, F.BCSR(block))
    pos, crd, tiles = t.levels[1].pos, t.levels[1].crd, t.vals
    c = rng.standard_normal(m).astype(np.float32)
    for impl in ("xla", "pallas"):
        y = np.asarray(ops.spmv_bcsr(pos, crd, tiles, c, impl=impl,
                                     interpret=True))[:n]
        np.testing.assert_allclose(y, dense @ c, atol=1e-3, rtol=1e-3)
    C = rng.standard_normal((m, 9)).astype(np.float32)
    for impl in ("xla", "pallas"):
        Y = np.asarray(ops.spmm_bcsr(pos, crd, tiles, C, impl=impl,
                                     interpret=True))[:n]
        np.testing.assert_allclose(Y, dense @ C, atol=1e-3, rtol=1e-3)
    Cs = rng.standard_normal((n, K)).astype(np.float32)
    Ds = rng.standard_normal((K, m)).astype(np.float32)
    bc_coords = t.block_coords()
    for impl in ("xla", "pallas"):
        out = np.asarray(ops.sddmm_bcsr(bc_coords[:, 0], bc_coords[:, 1],
                                        tiles, Cs, Ds, impl=impl,
                                        interpret=True))
        got = Tensor("o", t.shape, t.format, t.levels, out,
                     np.float32).to_dense()
        np.testing.assert_allclose(got, dense * (Cs @ Ds), atol=1e-3,
                                   rtol=1e-3)
    # fused blocked add (dense-tile output)
    triples, total = [(pos, crd, tiles)], dense.copy()
    for s in range(2):
        dd = ((rng.random((n, m)) < 0.2) *
              rng.standard_normal((n, m))).astype(np.float32)
        tt = Tensor.from_dense("X", dd, F.BCSR(block))
        triples.append((tt.levels[1].pos, tt.levels[1].crd, tt.vals))
        total += dd
    for impl in ("xla", "pallas"):
        got = np.asarray(ops.spadd3_bcsr_dense(*triples, n_rows=n, n_cols=m,
                                               impl=impl, interpret=True))
        np.testing.assert_allclose(got, total, atol=1e-3, rtol=1e-3)


def test_bcsr_spmd_builders():
    """Blocked shard_map builders wire up and match the vmap simulation."""
    from repro.distributed.executor import to_spmd
    rng = np.random.default_rng(2)
    dB = _operand(rng)
    B = Tensor.from_dense("B", dB, F.BCSR((2, 2)))
    cv = rng.standard_normal(M).astype(np.float32)
    c = Tensor.from_dense("c", cv)
    stmt = rc.parse_tin("a(i) = B(i,j) * c(j)",
                        a=Tensor.zeros_dense("a", (N,)), B=B, c=c)
    machine = rc.Machine(("x", 1))       # single-device CPU mesh
    for sched_fn in (default_row_schedule, default_nnz_schedule):
        k = lower(stmt, machine, schedule=sched_fn(stmt, machine))
        assert k.leaf_name.startswith("bcsr_spmv")
        np.testing.assert_allclose(to_spmd(k)(), dB @ cv, atol=1e-4)
    # spmm under both strategies (bcsr cells had working builders via the
    # conversion fallback before the direct path — keep that coverage)
    Cd = rng.standard_normal((M, 6)).astype(np.float32)
    C = Tensor.from_dense("C", Cd)
    stmt2 = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                         A=Tensor.zeros_dense("A", (N, 6)), B=B, C=C)
    for sched_fn in (default_row_schedule, default_nnz_schedule):
        k = lower(stmt2, machine, schedule=sched_fn(stmt2, machine))
        assert k.leaf_name.startswith("bcsr_spmm")
        np.testing.assert_allclose(to_spmd(k)(), dB @ Cd, atol=1e-3)
    # sddmm under both strategies
    Cs = Tensor.from_dense("C", rng.standard_normal((N, K)).astype(np.float32))
    Ds = Tensor.from_dense("D", rng.standard_normal((K, M)).astype(np.float32))
    A = Tensor.from_dense("A", (dB != 0) * 1.0, F.CSR())
    stmt3 = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)",
                         A=A, B=B, C=Cs, D=Ds)
    exp = dB * (np.asarray(Cs.to_dense()) @ np.asarray(Ds.to_dense()))
    for sched_fn in (default_row_schedule, default_nnz_schedule):
        k = lower(stmt3, machine, schedule=sched_fn(stmt3, machine))
        assert k.leaf_name.startswith("bcsr_sddmm")
        Bt = stmt3.rhs.accesses()[0].tensor
        tiles = to_spmd(k)()
        got = Tensor("o", Bt.shape, Bt.format, Bt.levels, tiles,
                     np.float32).to_dense()
        np.testing.assert_allclose(got, exp, atol=1e-3)


def test_from_blocks_roundtrip_and_dedupe():
    coords = np.array([[1, 0], [0, 1], [1, 0]])      # duplicate block
    tiles = np.stack([np.full((2, 2), v, np.float32) for v in (1, 2, 3)])
    t = Tensor.from_blocks("T", (4, 4), F.BCSR((2, 2)), coords, tiles)
    dense = t.to_dense()
    assert t.vals.shape == (2, 2, 2)                 # deduped
    np.testing.assert_allclose(dense[2:4, 0:2], np.full((2, 2), 4.0))
    np.testing.assert_allclose(dense[0:2, 2:4], np.full((2, 2), 2.0))
    # boundary padding stays out of the dense image
    t2 = Tensor.from_blocks("T2", (3, 3), F.BCSR((2, 2)),
                            np.array([[1, 1]]),
                            np.ones((1, 2, 2), np.float32))
    assert t2.to_dense().sum() == 1.0                # 3 of 4 cells padded


def test_spttv_output_format_follows_input():
    """DCSF input must yield a DCSR (not CSR) output — the row emitter
    reuses the input's level objects, the nnz emitter reassembles."""
    rng = np.random.default_rng(7)
    dims = (20, 15, 11)
    dB3 = ((rng.random(dims) < 0.1) *
           rng.standard_normal(dims)).astype(np.float32)
    cv = rng.standard_normal(dims[2]).astype(np.float32)
    machine = rc.Machine(("x", 4))
    for fm, want in ((F.CSF(3), "csr"), (F.DCSF(3), "dcsr")):
        for sched_fn in (default_row_schedule, default_nnz_schedule):
            B = Tensor.from_dense("B", dB3, fm)
            c = Tensor.from_dense("c", cv)
            A = Tensor.from_dense("A", np.zeros(dims[:2], np.float32),
                                  F.CSR())
            stmt = rc.parse_tin("A(i,j) = B(i,j,k) * c(k)", A=A, B=B, c=c)
            k = lower(stmt, machine, schedule=sched_fn(stmt, machine))
            res = k.run()
            assert F.format_key(res.format) == want
            np.testing.assert_allclose(
                res.to_dense(), np.einsum("ijk,k->ij", dB3, cv), atol=1e-4)


def test_spadd3_nnz_stream_reused_on_replan():
    """The concatenated addend stream is packed by the materialization
    layer and cached, so re-lowering over the same operands (a straggler
    re-plan) reuses it instead of re-walking the coordinate trees."""
    rng = np.random.default_rng(9)
    fm = F.CSR()
    Bt = Tensor.from_dense("B", _operand(rng), fm)
    Ct = Tensor.from_dense("C", _operand(rng), fm)
    Dt = Tensor.from_dense("D", _operand(rng), fm)
    A = Tensor.from_dense("A", np.zeros((N, M), np.float32), F.CSR())
    stmt = rc.parse_tin("A(i,j) = B(i,j) + C(i,j) + D(i,j)",
                        A=A, B=Bt, C=Ct, D=Dt)
    machine = rc.Machine(("x", 4))
    P.ADD_STREAM_STATS.update(hits=0, misses=0)
    k1 = lower(stmt, machine, schedule=default_nnz_schedule(stmt, machine))
    k2 = lower(stmt, machine, schedule=default_nnz_schedule(stmt, machine))
    assert P.ADD_STREAM_STATS["misses"] == 1
    assert P.ADD_STREAM_STATS["hits"] == 1
    expected = Bt.to_dense() + Ct.to_dense() + Dt.to_dense()
    np.testing.assert_allclose(k2.run().to_dense(), expected, atol=1e-4)
    assert "_addstream" in k1.shards
    assert k1.shards["_addstream"].kind == "add_stream"
    # in-place operand mutation must INVALIDATE the cache (fingerprint),
    # not serve stale values
    Bt.vals[:] = Bt.vals * 10.0
    k3 = lower(stmt, machine, schedule=default_nnz_schedule(stmt, machine))
    assert P.ADD_STREAM_STATS["misses"] == 2
    expected3 = Bt.to_dense() + Ct.to_dense() + Dt.to_dense()
    np.testing.assert_allclose(k3.run().to_dense(), expected3, atol=1e-4)
