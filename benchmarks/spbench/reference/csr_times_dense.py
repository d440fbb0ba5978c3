"""``A(i,j) = B(i,k) * C(k,j)`` with B in CSR and C dense (``J = 1`` is
SpMV): the operands of a run, the statement, its compulsory work, and the
plain reference with its control.

A configuration names this module by its ``reference`` key. Its keys read
here: ``statement`` (the index notation), ``output``, ``sparse`` and
``dense`` (the tensors' names), ``dense_cols`` (J).

The reference is scipy in float64 over the operand's own ``pos``, ``crd``
and ``vals``; it imports nothing of the program under test. The control is
the same reference with ``B`` and ``C`` rounded to bfloat16 before an
exact (float64) accumulation: the mildest form of the bf16-operand step
that would tempt a later change. It must fail the limit.

The work is counted from the statement and its operands alone, never from
the schedule, the leaf or the kernel that implements it, so that a row or
a non-zero schedule, a gather leaf or a Pallas kernel are all held to the
same yardstick:

- bytes: ``nnz * (4 crd + 4 val) + (n_rows + 1) * 4 pos``, plus the dense
  operand read once (``n_cols * J * 4``) and the output written once
  (``n_rows * J * 4``);
- operations: ``2 * nnz * J`` (one multiply and one add per stored entry
  and output column).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

from compare import componentwise_error

#: Stream of the seed for the dense operands (the graph uses its own).
DENSE_STREAM = 2
INDEX_BYTES = 4
VALUE_BYTES = 4


def operands(cfg: dict, B, size: int, seed: int) -> list:
    """``size`` dense operands C, one row per column of ``B``, standard
    normal float32."""
    rng = np.random.default_rng([DENSE_STREAM, seed])
    n, j = B.shape[1], int(cfg["dense_cols"])
    shape = (n,) if j == 1 else (n, j)
    return [rng.standard_normal(shape, dtype=np.float32)
            for _ in range(size)]


def statement(cfg: dict, B, dense):
    """The program's statement over ``B`` and the first dense operand."""
    import repro.core as rc
    from repro.core.tensor import Tensor
    out_shape = (B.shape[0],) + dense.shape[1:]
    return rc.parse_tin(cfg["statement"], **{
        cfg["output"]: Tensor.zeros_dense(cfg["output"], out_shape),
        cfg["sparse"]: B,
        cfg["dense"]: Tensor.from_dense(cfg["dense"], dense)})


def sizes(cfg: dict, B) -> dict:
    """The sizes a run logs beside its kernels."""
    return {"nnz": int(B.nnz), "shape": list(B.shape),
            "dense_cols": int(cfg["dense_cols"])}


def count(n_rows: int, n_cols: int, nnz: int, j: int) -> dict:
    sparse = nnz * (INDEX_BYTES + VALUE_BYTES) + (n_rows + 1) * INDEX_BYTES
    dense = n_cols * j * VALUE_BYTES
    out = n_rows * j * VALUE_BYTES
    return {"bytes": sparse + dense + out, "flops": 2 * nnz * j}


def work(cfg: dict, B) -> dict:
    """Compulsory work of one iteration, whatever the schedule."""
    return count(B.shape[0], B.shape[1], int(B.nnz), int(cfg["dense_cols"]))


def scipy_csr(B, dtype=np.float64):
    pos, crd = B.levels[1].pos, B.levels[1].crd
    return sp.csr_matrix((B.vals.astype(dtype), crd, pos), shape=B.shape)


def _as_2d(x):
    return x.reshape(x.shape[0], -1)


class Reference:
    """Float64 results of one operand against each dense operand asked."""

    def __init__(self, B):
        self.B = scipy_csr(B)
        self.absB = abs(self.B)
        self._expected = {}

    def expected(self, dense: np.ndarray) -> tuple:
        """(B @ dense, |B| @ |dense|) in float64, kept per operand."""
        key = id(dense)
        if key not in self._expected:
            d = dense.astype(np.float64)
            self._expected[key] = (dense, _as_2d(self.B @ d),
                                   _as_2d(self.absB @ np.abs(d)))
        return self._expected[key][1:]

    def error(self, got, dense: np.ndarray) -> float:
        """Componentwise error of ``got`` against ``B @ dense``."""
        got = np.asarray(got)
        if got.shape != (self.B.shape[0],) + dense.shape[1:]:
            return float("inf")
        return componentwise_error(got, *self.expected(dense))

    def control(self, dense: np.ndarray) -> np.ndarray:
        """The reference computed from bfloat16-rounded operands."""
        Bq = self.B.copy()
        Bq.data = Bq.data.astype(ml_dtypes.bfloat16).astype(np.float64)
        dq = dense.astype(ml_dtypes.bfloat16).astype(np.float64)
        return Bq @ dq

    def control_error(self, dense: np.ndarray) -> float:
        """The compared number, read off the control in the program's
        place."""
        return self.error(self.control(dense), dense)
