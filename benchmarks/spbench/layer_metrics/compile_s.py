"""Host seconds of XLA compilation over set-up: the program's counter
``jit.compile_s`` (``runtime/telemetry.py``), the length of every compile
of the process, or of its read from the persistent compilation cache."""


def read(ctx):
    s = ctx["setup_counters"].get("jit.compile_s")
    return s if s else None
