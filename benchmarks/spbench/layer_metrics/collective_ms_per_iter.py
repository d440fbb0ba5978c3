"""Device milliseconds per iteration in collective ops (all-reduce and
kin) on the busiest device; nothing where the trace holds none."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    coll = tr["per_device"][tr["busiest"]]["collective_s"]
    return coll / ctx["iterations"] * 1e3 if coll > 0 else None
