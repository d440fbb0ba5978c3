"""The statement's work count against hand counts, the same count for
every schedule, and the roofline over it."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(HERE), "..", "..", "src")]

import spec  # noqa: E402
import work  # noqa: E402
from peaks import PEAKS  # noqa: E402

CSR = spec.module("reference", "csr_times_dense")


def test_csr_spmv_hand_count():
    # 3x4 CSR with 5 stored entries, J = 1:
    # crd + vals 5 * 8 = 40, pos 4 * 4 = 16, c 4 * 4 = 16, a 3 * 4 = 12.
    w = CSR.count(3, 4, 5, 1)
    assert w == {"bytes": 40 + 16 + 16 + 12, "flops": 10}


def test_csr_spmm_hand_count():
    # J = 2: dense 4 * 2 * 4 = 32, out 3 * 2 * 4 = 24, flops 2 * 5 * 2.
    w = CSR.count(3, 4, 5, 2)
    assert w == {"bytes": 40 + 16 + 32 + 24, "flops": 20}


def test_roofline_takes_the_larger_bound_and_shares_over_chips():
    peaks = {"flops": 10.0, "hbm_bytes_per_s": 100.0}
    assert work.roofline_s({"bytes": 1000, "flops": 5}, peaks, 1) == 10.0
    assert work.roofline_s({"bytes": 10, "flops": 50}, peaks, 1) == 5.0
    assert work.roofline_s({"bytes": 1000, "flops": 5}, peaks, 4) == 2.5


def test_rows_and_nnz_cells_count_the_same_work():
    """The count depends on the statement and the operand, never on the
    schedule: both arxiv cells share one configuration and one count."""
    bench = spec.load()
    rows = spec.cell(bench, "arxiv_gcn_spmm_rows")
    nnz = spec.cell(bench, "arxiv_gcn_spmm_nnz")
    assert rows["traffic"]["schedule"] != nnz["traffic"]["schedule"]
    counts = []
    for cell in (rows, nnz):
        cfg = dict(cell["config"], scale=10, nodes=700, edges=3000)
        B = cell["generator"].generate(cfg, 5)
        counts.append(cell["reference"].work(cfg, B))
    assert counts[0] == counts[1]
    assert counts[0]["flops"] == 2 * B.nnz * 128


def test_peak_table_has_v5e_with_published_numbers():
    p = PEAKS["TPU v5 lite"]
    assert (p["flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == \
        (197e12, 819e9, 16e9)
