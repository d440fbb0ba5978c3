"""The comparison that decides ``correct``, shared by every statement
whose output entries are sums of products.

The compared number is the componentwise error

    max over entries e of |got(e) - ref(e)| / den(e),

with ``ref`` the float64 reference and ``den`` the same sum taken over
the magnitudes of its terms (``|B| |C|`` for ``B * C``): the error of
each output entry against the size of its own terms, which float32
accumulation keeps near ``sqrt(terms) * 2**-24``. An entry with no terms
(``den == 0``) must read exactly 0. Every entry counts, so a wrong entry
of one term shows as plainly as a wrong sum of thousands.
"""
from __future__ import annotations

import numpy as np


def componentwise_error(got, ref: np.ndarray, den: np.ndarray) -> float:
    """``inf`` for a wrong shape, a non-finite entry or a non-zero entry
    with no terms."""
    got = np.asarray(got)
    if got.size != ref.size or got.shape[0] != ref.shape[0]:
        return float("inf")
    diff = np.abs(got.reshape(ref.shape).astype(np.float64) - ref)
    if not np.isfinite(diff).all():
        return float("inf")
    zero = den == 0
    if (diff[zero] != 0).any():
        return float("inf")
    return float((diff / np.where(zero, 1.0, den)).max())
