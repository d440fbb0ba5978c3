"""One run of one cell: set-up, the measured window, the check, the result.

A cell is a configuration (the sparse operand and its statement) under a
traffic mix (the machine, the schedule and the loop the window drives).
Every piece is found by name (:mod:`spec`): the operand's generator
(``data/``), the statement's operands, work and plain reference
(``reference/``), the loop the window drives (``loops/``) and the
per-layer readers (``layer_metrics/``). Nothing here names a cell, a
statement or a loop: the loop lowers what its step needs
(``make(cfg, B, pool, mix, parts) -> (step, devices, kernels)``), and
the harness logs every kernel and refuses a run in which one fell back.

A reader gets ``ctx``: the program's spans over set-up (``spans``), the
deltas of its counters over set-up and over the window
(``setup_counters``, ``counters``), the window's iterations, the loop's
rebind seconds, the reduced trace (:func:`devtrace.reduce`), the
statement's work, the chip's peaks and the number of chips.

An iteration ends when ``jax.block_until_ready`` returns on its result.
After the window, the outputs of a sample of iterations drawn from the
seed are copied to the host and compared with the float64 reference.
"""
from __future__ import annotations

import gc
import json
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

import devtrace

#: Stream of the seed for the sample of iterations that is checked.
SAMPLE_STREAM = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class Parts:
    """Host-clock seconds of the named parts of set-up."""

    def __init__(self):
        self.parts = {}

    def time(self, name, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.parts[name] = self.parts.get(name, 0.0) + \
            time.perf_counter() - t0
        return out


class Sample:
    """Uniform sample of ``k`` iterations' outputs (reservoir), drawn from
    the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([SAMPLE_STREAM, seed])
        self.items = []
        self.seen = 0

    def offer(self, item):
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = item


def _window(step, seconds: float, sample: Sample):
    """Iterations until ``seconds`` have passed; returns per-iteration
    seconds, rebind seconds and the window's length."""
    from jax.profiler import TraceAnnotation
    import jax
    iters, rebinds = [], []
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while True:
            ts = time.perf_counter()
            y, p, rb = step(i)
            jax.block_until_ready(y)
            te = time.perf_counter()
            with TraceAnnotation("bench.result"):
                sample.offer((i, p, y))
            iters.append(te - ts)
            if rb is not None:
                rebinds.append(rb)
            i += 1
            if time.perf_counter() >= deadline:
                break
        t_end = time.perf_counter()
    return iters, rebinds, t_end - t0


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _metric(value, unit):
    return {"value": value, "unit": unit}


def _counters() -> dict:
    from repro.runtime import telemetry
    return telemetry.METRICS.snapshot()["counters"]


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def _check_kernels(kernels) -> None:
    """Log every kernel the loop lowered; refuse the run if any of them
    fell back to a format conversion or a generic leaf."""
    for k in kernels:
        log("lowered " + json.dumps({
            "cell": k.cell_id(), "leaf": k.leaf_name,
            "fallbacks": k.fallbacks}))
        log("comm (planned bytes) " + json.dumps(k.comm.as_dict()))
    bad = [(k.leaf_name, k.fallbacks) for k in kernels
           if k.fallbacks or k.leaf_name.startswith("generic")]
    if bad or not kernels:
        raise RuntimeError(f"cell did not lower directly: {bad}")


class _Compiles:
    """XLA compiles in the process, counted whether or not the program's
    tracer is on, so the log shows that none falls in the window."""
    count = 0
    _listening = False

    @classmethod
    def listen(cls) -> None:
        import jax
        if not cls._listening:
            jax.monitoring.register_event_duration_secs_listener(
                cls._on_event)
            cls._listening = True

    @classmethod
    def _on_event(cls, event, seconds, **attrs):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.count += 1


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None) -> dict:
    """Run one cell; returns the result object (the last stdout line).

    The program's tracer records through set-up and warm-up, and through
    the window only when ``trace`` is set: an untraced window runs the
    program as a user does."""
    from repro.runtime import telemetry
    _Compiles.listen()
    telemetry.TRACER.clear()
    telemetry.TRACER.enable()
    try:
        return _run(cell, seed, seconds, trace, t_start, peaks)
    finally:
        telemetry.TRACER.disable()


def _run(cell, seed, seconds, trace, t_start, peaks):
    import jax
    from repro.runtime import telemetry

    cfg, mix = cell["config"], cell["traffic"]
    stmt_mod = cell["reference"]
    parts = Parts()
    counters0 = _counters()
    B = parts.time("generate_s", cell["generator"].generate, cfg, seed,
                   cfg["sparse"])
    pool = parts.time("operands_s", stmt_mod.operands, cfg, B,
                      int(mix["operand_pool"]), seed)
    log("operands " + json.dumps(stmt_mod.sizes(cfg, B)))
    step, devices, kernels = cell["loop"].make(cfg, B, pool, mix, parts)
    _check_kernels(kernels)
    devices = devices or [jax.devices()[0]]
    if len(devices) != int(mix["chips"]):
        raise RuntimeError(f"the loop runs on {len(devices)} devices, the "
                           f"traffic asks for {mix['chips']}")
    for i in range(int(mix["warmup_iterations"])):
        y, _, _ = parts.time("warmup_s", step, i)
        parts.time("warmup_s", jax.block_until_ready, y)
    del y
    gc.collect()
    setup_s = time.perf_counter() - t_start
    spans = telemetry.TRACER.spans()
    counters1 = _counters()
    setup_counters = _delta(counters0, counters1)
    if not trace:
        telemetry.TRACER.disable()
    lower_spans = {}
    for s in spans:
        if s["dur_us"] is not None and s["name"].startswith("lower"):
            lower_spans[s["name"]] = lower_spans.get(s["name"], 0.0) + \
                s["dur_us"] * 1e-6
    log("lower spans (s) " + json.dumps(lower_spans))
    log("setup counters " + json.dumps(setup_counters))
    log("setup " + json.dumps({"setup_s": setup_s, **parts.parts}))

    sample = Sample(int(mix["check_sample"]), seed)
    trace_dir = tempfile.mkdtemp(prefix="spbench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    compiles0 = _Compiles.count
    try:
        iters, rebinds, window_s = _window(step, seconds, sample)
    finally:
        if trace:
            jax.profiler.stop_trace()
    telemetry.TRACER.disable()
    counters = _delta(counters1, _counters())
    peak = _peak_bytes(devices)
    n = len(iters)
    iter_ms = window_s / n * 1e3
    p95_ms = float(np.percentile(np.asarray(iters), 95)) * 1e3
    log("window " + json.dumps({
        "iterations": n, "window_s": window_s, "iter_ms": iter_ms,
        "iter_p95_ms": p95_ms,
        "iter_median_ms": statistics.median(iters) * 1e3,
        "iter_max_ms": max(iters) * 1e3, "memory_peak_bytes": peak,
        "compiles": _Compiles.count - compiles0}))
    if trace:
        log("window counters " + json.dumps(counters))

    reduced = None
    if trace:
        try:
            loaded = devtrace.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        planes = [f"/device:TPU:{d.id}" for d in devices]
        if all(p in loaded for p in planes):
            reduced = devtrace.reduce(loaded, planes)
            log("trace " + json.dumps({
                k: reduced[k] for k in ("window_s", "per_device",
                                        "busiest", "modules",
                                        "idle_by_span")}))
        elif peaks is not None:
            raise RuntimeError(f"the trace holds no ops of {planes}; "
                               f"planes: {sorted(loaded)}")

    del step, kernels
    gc.collect()
    t_check = time.perf_counter()
    ref = stmt_mod.Reference(B)
    limit = float(cfg["err_limit"])
    errs = [(i, p, ref.error(y, pool[p])) for i, p, y in sample.items]
    err = max(e for _, _, e in errs)
    failed = sum(1 for *_, e in errs if not e <= limit)
    log("check " + json.dumps({
        "checked_iterations": [i for i, _, _ in errs],
        "errors": [e for *_, e in errs],
        "check_s": time.perf_counter() - t_check}))

    work = stmt_mod.work(cfg, B)
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": failed == 0 and n > 0, "attempted": n,
              "failed": failed}
    units = {m["name"]: m["unit"] for m in
             cell["end_to_end"] + cell["per_layer"]}
    if trace:
        ctx = {"spans": spans, "setup_counters": setup_counters,
               "counters": counters, "iterations": n, "rebind_s": rebinds,
               "trace": reduced, "work": work, "peaks": peaks,
               "chips": len(devices)}
        metrics = {}
        for name, reader in cell["readers"].items():
            v = reader.read(ctx)
            if v is not None:
                metrics[name] = _metric(v, units[name])
        if reduced is not None:
            per = reduced["per_device"].values()
            device["busy_s"] = sum(d["busy_s"] for d in per) / len(per)
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = {"iter_ms": iter_ms, "iter_p95_ms": p95_ms,
               "setup_s": setup_s,
               "peak_hbm_mb": None if peak is None else peak / 1e6}
        metrics = {m["name"]: _metric(e2e[m["name"]], m["unit"])
                   for m in cell["end_to_end"]
                   if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {"rel_err": {"value": err, "limit": limit}}
    print(f"check rel_err {err!r} limit {limit!r}", file=sys.stderr,
          flush=True)
    return result
