"""The roofline of a cell's compulsory work. The work itself (bytes and
operations of one iteration) is counted by the statement's reference
module (``reference/<name>.py``, function ``work``) from the statement
and its operands alone, never from the schedule."""
from __future__ import annotations


def roofline_s(work: dict, peaks: dict, chips: int) -> float:
    """Least time ``chips`` chips could take: the larger of bytes over HBM
    bandwidth and operations over peak, each shared over the chips."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["flops"]) / chips
