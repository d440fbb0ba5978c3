"""Host milliseconds per iteration in ``rebind_dense`` (dense operand
re-packing and runner re-emit), from the benchmark's own host-clock span
around the call (also written to the trace as ``bench.rebind``)."""


def read(ctx):
    rb = ctx["rebind_s"]
    return sum(rb) / len(rb) * 1e3 if rb else None
