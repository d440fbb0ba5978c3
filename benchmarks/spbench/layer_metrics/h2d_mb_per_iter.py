"""Megabytes placed on the chip per iteration by the kernel calls: the
program's counter ``run.h2d_bytes`` over the traced window, over its
iterations. Nothing where the calls placed no bytes."""


def read(ctx):
    b = ctx["counters"].get("run.h2d_bytes")
    return b / ctx["iterations"] / 1e6 if b else None
