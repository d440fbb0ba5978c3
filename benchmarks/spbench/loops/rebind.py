"""A pool of dense operands is made in set-up; each iteration binds the
next one with ``rebind_dense`` and calls ``run()``, as a solver or a GNN
layer brings a new right-hand side every step. A memoised result cannot
pass for a fast one."""
import time

import lowering


def make(cfg, B, pool, mix, parts):
    """(step, devices, kernels): step(i) -> (output, pool index, rebind
    seconds); ``devices`` None means the default device."""
    from jax.profiler import TraceAnnotation
    from repro.core import lower as lower_mod
    from repro.core.tensor import Tensor
    kernel = lowering.lower_statement(cfg, B, pool[0], mix, parts)
    name = cfg["dense"]
    tensors = [Tensor.from_dense(name, d) for d in pool]

    def step(i):
        p = i % len(tensors)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.rebind"):
            k = lower_mod.rebind_dense(kernel, {name: tensors[p]})
        t1 = time.perf_counter()
        with TraceAnnotation("bench.run"):
            y = k.run()
        return y, p, t1 - t0

    return step, None, [kernel]
