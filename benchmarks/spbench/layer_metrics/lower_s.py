"""Host seconds of ``lower()``: the program's own ``lower`` span
(``runtime/telemetry.py``), summed over the lowerings of set-up. Its
``lower.plan/.materialize/.emit/.jit`` children are printed on an earlier
line of the run."""


def read(ctx):
    durs = [s["dur_us"] for s in ctx["spans"]
            if s["name"] == "lower" and s["dur_us"] is not None]
    return sum(durs) * 1e-6 if durs else None
