"""Megabytes the kernel calls returned per iteration: the program's
counter ``run.d2h_bytes`` over the traced window, over its iterations."""


def read(ctx):
    b = ctx["counters"].get("run.d2h_bytes")
    return b / ctx["iterations"] / 1e6 if b else None
