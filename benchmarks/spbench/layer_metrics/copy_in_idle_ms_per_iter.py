"""Milliseconds per iteration in which the busiest device is idle while
the program places a call's host arrays on it: the idle time charged to
the span ``run.copy_in`` (:func:`devtrace.idle_by_span`)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "run.copy_in" not in tr["idle_by_span"]:
        return None
    return tr["idle_by_span"]["run.copy_in"] / ctx["iterations"] * 1e3
