"""Milliseconds per iteration in which the busiest device is idle while a
kernel call copies its output back and assembles it on the host: the idle
time charged to the span ``run`` itself, outside its children
``run.copy_in`` and ``run.execute`` (:func:`devtrace.idle_by_span`)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "run" not in tr["idle_by_span"]:
        return None
    return tr["idle_by_span"]["run"] / ctx["iterations"] * 1e3
