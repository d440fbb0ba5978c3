"""The seeded R-MAT generator: determinism, quadrant shares, CSR shape."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(HERE), "..", "..", "src")]

import spec  # noqa: E402

GEN = spec.module("data", "rmat_csr")
G500 = {"scale": 11, "edgefactor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
        "symmetric": True, "self_loops": "dropped",
        "values": "uniform_weight"}
GCN = {"scale": 11, "nodes": 1500, "edges": 9000,
       "initiator": [0.57, 0.19, 0.19, 0.05], "symmetric": True,
       "self_loops": "added", "values": "gcn_norm"}


def _same(a, b):
    return (a.shape == b.shape and np.array_equal(a.vals, b.vals)
            and np.array_equal(a.levels[1].pos, b.levels[1].pos)
            and np.array_equal(a.levels[1].crd, b.levels[1].crd))


@pytest.mark.parametrize("cfg", [G500, GCN], ids=["graph500", "gcn"])
def test_same_seed_same_operand_other_seed_other(cfg):
    big = 2**31 + 12345
    assert _same(GEN.generate(cfg, big), GEN.generate(cfg, big))
    assert not _same(GEN.generate(cfg, big), GEN.generate(cfg, big + 1))


def test_quadrant_shares_follow_the_initiator():
    rng = np.random.default_rng(0)
    a, b, c, d = G500["initiator"]
    src, dst = GEN.kronecker_edges(8, 200_000, G500["initiator"], rng)
    for level in (0, 4, 7):
        ii, jj = (src >> level) & 1, (dst >> level) & 1
        shares = [np.mean((ii == x) & (jj == y))
                  for x, y in ((0, 0), (0, 1), (1, 0), (1, 1))]
        np.testing.assert_allclose(shares, [a, b, c, d], atol=0.005)


def test_graph500_operand_is_symmetric_without_loops_or_repeats():
    B = GEN.generate(G500, 3)
    n = 1 << G500["scale"]
    pos, crd = B.levels[1].pos, B.levels[1].crd
    rows = np.repeat(np.arange(n), np.diff(pos))
    assert B.shape == (n, n) and pos[-1] == B.nnz
    assert not (rows == crd).any()
    keys = rows.astype(np.int64) * n + crd
    assert (np.diff(keys) > 0).all()          # sorted, no repeats
    dense = np.zeros((n, n), np.float32)
    dense[rows, crd] = B.vals
    np.testing.assert_array_equal(dense, dense.T)
    assert (B.vals > 0).all() and (B.vals <= 1).all()
    # skew: the hub holds far more than the mean degree
    assert np.diff(pos).max() > 20 * B.nnz / n


def test_gcn_operand_has_self_loops_and_normalised_values():
    B = GEN.generate(GCN, 4)
    n = GCN["nodes"]
    pos, crd = B.levels[1].pos, B.levels[1].crd
    rows = np.repeat(np.arange(n), np.diff(pos))
    assert B.shape == (n, n)
    assert ((rows == crd).sum()) == n
    deg = np.diff(pos).astype(np.float64)
    np.testing.assert_allclose(B.vals, 1 / np.sqrt(deg[rows] * deg[crd]),
                               rtol=1e-6)


def test_unknown_value_kind_is_refused():
    with pytest.raises(ValueError):
        GEN.generate(dict(G500, values="ones"), 0)
