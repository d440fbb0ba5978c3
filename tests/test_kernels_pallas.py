"""Per-kernel Pallas validation: shape/dtype sweeps, interpret=True vs the
ref.py pure-jnp oracles (deliverable c)."""
import numpy as np
import pytest

from repro.core import formats as F
from repro.core.tensor import Tensor
from repro.kernels import ops

SHAPES_2D = [(8, 8), (37, 53), (64, 128), (130, 65), (1, 7), (256, 17)]
DENSITIES = [0.05, 0.3]
DTYPES = [np.float32]


def _csr(rng, n, m, density, dtype):
    dense = ((rng.random((n, m)) < density) *
             rng.standard_normal((n, m))).astype(dtype)
    t = Tensor.from_dense("B", dense, F.CSR())
    return t, dense


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("density", DENSITIES)
def test_spmv_pallas_sweep(shape, density):
    rng = np.random.default_rng(hash(shape) % 2**31)
    n, m = shape
    t, dense = _csr(rng, n, m, density, np.float32)
    c = rng.standard_normal(m).astype(np.float32)
    pos, crd = t.levels[1].pos, t.levels[1].crd
    ref = np.asarray(ops.spmv(pos, crd, t.vals, c, impl="xla"))
    got = np.asarray(ops.spmv(pos, crd, t.vals, c, impl="pallas",
                              interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(ref, dense @ c, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", SHAPES_2D[:4])
def test_spmv_nnz_pallas_sweep(shape):
    rng = np.random.default_rng(1)
    n, m = shape
    t, dense = _csr(rng, n, m, 0.25, np.float32)
    pos, crd = t.levels[1].pos, t.levels[1].crd
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(pos))
    c = rng.standard_normal(m).astype(np.float32)
    got = np.asarray(ops.spmv_nnz(rows, crd, t.vals, c, n_rows=n,
                                  impl="pallas", interpret=True))
    np.testing.assert_allclose(got, dense @ c, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", SHAPES_2D[:4])
@pytest.mark.parametrize("j", [1, 16, 130])
def test_spmm_pallas_sweep(shape, j):
    rng = np.random.default_rng(2)
    n, m = shape
    t, dense = _csr(rng, n, m, 0.2, np.float32)
    C = rng.standard_normal((m, j)).astype(np.float32)
    pos, crd = t.levels[1].pos, t.levels[1].crd
    got = np.asarray(ops.spmm(pos, crd, t.vals, C, impl="pallas",
                              interpret=True))
    np.testing.assert_allclose(got, dense @ C, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", SHAPES_2D[:4])
@pytest.mark.parametrize("K", [4, 32])
def test_sddmm_pallas_sweep(shape, K):
    rng = np.random.default_rng(3)
    n, m = shape
    t, dense = _csr(rng, n, m, 0.2, np.float32)
    pos, crd = t.levels[1].pos, t.levels[1].crd
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(pos))
    C = rng.standard_normal((n, K)).astype(np.float32)
    D = rng.standard_normal((K, m)).astype(np.float32)
    got = np.asarray(ops.sddmm(rows, crd, t.vals, C, D, impl="pallas",
                               interpret=True))
    exp = t.vals * (C[rows] * D[:, crd].T).sum(1)
    np.testing.assert_allclose(got, exp, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", [(16, 24), (65, 40)])
def test_spadd3_pallas_sweep(shape):
    rng = np.random.default_rng(4)
    n, m = shape
    ts = []
    total = np.zeros((n, m), np.float32)
    for i in range(3):
        t, dense = _csr(rng, n, m, 0.1 + 0.05 * i, np.float32)
        ts.append((t.levels[1].pos, t.levels[1].crd, t.vals))
        total += dense
    got = np.asarray(ops.spadd3_dense(*ts, n_rows=n, n_cols=m,
                                      impl="pallas", interpret=True))
    np.testing.assert_allclose(got, total, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dims", [(10, 8, 6), (25, 13, 9)])
def test_spttv_spmttkrp_pallas_sweep(dims):
    rng = np.random.default_rng(5)
    L = 6
    dB = ((rng.random(dims) < 0.15) *
          rng.standard_normal(dims)).astype(np.float32)
    t = Tensor.from_dense("B", dB, F.CSF(3))
    p1, c1 = t.levels[1].pos, t.levels[1].crd
    p2, c2 = t.levels[2].pos, t.levels[2].crd
    cv = rng.standard_normal(dims[2]).astype(np.float32)
    tv = np.asarray(ops.spttv(p1, c1, p2, c2, t.vals, cv, impl="pallas",
                              interpret=True))
    i_of_ij = np.repeat(np.arange(dims[0]), np.diff(p1))
    got = np.zeros(dims[:2], np.float32)
    got[i_of_ij, c1] = tv
    np.testing.assert_allclose(got, np.einsum("ijk,k->ij", dB, cv),
                               atol=1e-4, rtol=1e-4)

    C = rng.standard_normal((dims[1], L)).astype(np.float32)
    D = rng.standard_normal((dims[2], L)).astype(np.float32)
    mk = np.asarray(ops.spmttkrp(p1, c1, p2, c2, t.vals, C, D,
                                 impl="pallas", interpret=True))
    np.testing.assert_allclose(mk, np.einsum("ijk,jl,kl->il", dB, C, D),
                               atol=1e-3, rtol=1e-3)


def test_ell_padding_waste_reported():
    from repro.kernels.layout import ell_pack
    rng = np.random.default_rng(6)
    t, _ = _csr(rng, 64, 64, 0.1, np.float32)
    blocks, = ell_pack(t.levels[1].pos, t.levels[1].crd, t.vals)
    assert 0.0 <= blocks.padding_waste() < 1.0
