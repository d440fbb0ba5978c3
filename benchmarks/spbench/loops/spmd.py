"""``to_spmd(kernel)`` places the shards on the mesh once, in set-up (its
seconds are the set-up part ``place_s``); each iteration calls the
returned callable with the operands it placed."""
import lowering


def make(cfg, B, pool, mix, parts):
    """(step, devices, kernels): step(i) -> (output, pool index 0,
    None)."""
    from jax.profiler import TraceAnnotation
    from repro.distributed.executor import to_spmd
    kernel = lowering.lower_statement(cfg, B, pool[0], mix, parts)
    call = parts.time("place_s", to_spmd, kernel)

    def step(i):
        with TraceAnnotation("bench.run"):
            y = call()
        return y, 0, None

    devices = call.placed[0].sharding.mesh.devices.flatten().tolist()
    return step, devices, [kernel]
