"""Reduction of a profiler trace to the benchmark's device numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain structure, ``{plane: {line: [[name, start_ns, dur_ns], ...]}}``,
keeping the device planes' op and module lines and the host spans of the
benchmark (``bench.*``) and of the program's kernel calls (``run``,
``run.copy_in``, ``run.execute``). The reductions below work on that
structure alone, so a small recorded trace checks them without a chip.

- busy time: the union of op intervals on a device inside the window;
- idle share: 1 - busy / window;
- collective time: the union of the intervals of collective ops, on the
  op line and on the line of asynchronous ops;
- modules: device seconds per compiled module (``jit_<leaf>``);
- idle by span: the idle time cut at host span boundaries, each piece
  charged to the innermost span open over it (``none`` outside them all);
- breakdown: the ops that took most time, and the longest idle gaps,
  each labelled by the innermost host span open at its middle.

Device and host events of one trace share a clock only to within a few
milliseconds (a TPU v5e trace put the device's first op about 1.5 ms
before the host call that launched it); against windows of seconds that
is well under a thousandth.
"""
from __future__ import annotations

import glob
import os
import re

#: Op lines of a TPU device plane: the ops, and the asynchronous ops
#: (copies, collectives) that run beside them.
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
#: Line of the compiled programs (modules) a device ran.
MODULE_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
#: Host spans the benchmark writes (``jax.profiler.TraceAnnotation``) and
#: the program's kernel-call spans (``runtime/telemetry.py``).
HOST_SPAN = re.compile(r"^(bench\.|run$|run\.)")
#: A module event's name may end in its program id: ``jit_f(1234)``.
MODULE_ID = re.compile(r"\(\d+\)$")
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum", re.IGNORECASE)


def load(trace_dir: str) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {paths}")
    from jax.profiler import ProfileData
    data = ProfileData.from_file(paths[0])
    out = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            keep = lambda line, ev: line in (OP_LINE, ASYNC_LINE,  # noqa
                                             MODULE_LINE)
        elif plane.name == HOST_PLANE:
            keep = lambda line, ev: HOST_SPAN.match(ev)  # noqa
        else:
            continue
        lines = {}
        for line in plane.lines:
            evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for ev in line.events if keep(line.name, ev.name)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        out[plane.name] = lines
    return out


def ops(trace: dict, device: str) -> list:
    return trace[device].get(OP_LINE, [])


def host_spans(trace: dict) -> list:
    return sorted((ev for evs in trace.get(HOST_PLANE, {}).values()
                   for ev in evs), key=lambda e: e[1])


def window(trace: dict) -> tuple:
    """(start_ns, end_ns) of the benchmark's measured window."""
    spans = [e for e in host_spans(trace) if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    _, t0, dur = spans[0]
    return t0, t0 + dur


def _merged(events, t0, t1):
    """Union of event intervals clipped to [t0, t1], as sorted pairs."""
    iv = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                if s + d > t0 and s < t1)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, t0, t1) -> float:
    return float(sum(e - s for s, e in _merged(events, t0, t1)))


def collective_ns(events, t0, t1) -> float:
    return busy_ns([e for e in events if COLLECTIVE.search(e[0])], t0, t1)


def short_name(op: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``%fusion.1``."""
    return op.split(" = ", 1)[0]


def _sum_by_name(events, t0, t1, name_of) -> dict:
    """Nanoseconds of the events inside [t0, t1], summed by
    ``name_of(name)``."""
    tot = {}
    for name, s, d in events:
        if s + d > t0 and s < t1:
            key = name_of(name)
            tot[key] = tot.get(key, 0.0) + min(s + d, t1) - max(s, t0)
    return tot


def top_ops(events, t0, t1, k=10) -> list:
    best = sorted(_sum_by_name(events, t0, t1, short_name).items(),
                  key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def module_s(events, t0, t1) -> dict:
    """Device seconds of each module inside [t0, t1], by module name."""
    tot = _sum_by_name(events, t0, t1, lambda n: MODULE_ID.sub("", n))
    return {name: ns * 1e-9 for name, ns in tot.items()}


def _gaps(events, t0, t1) -> list:
    """The idle intervals of [t0, t1], in order."""
    gaps, cur = [], t0
    for s, e in _merged(events, t0, t1):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def idle_by_span(events, spans, t0, t1) -> dict:
    """Idle seconds in [t0, t1] cut at the boundaries of the host spans
    (other than the window), each piece charged to the innermost (shortest)
    span open over it, and to ``none`` outside every span. Every span open
    in the window has an entry; the entries add up to the idle time."""
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN and sp[2] > 0
             and sp[1] < t1 and sp[1] + sp[2] > t0]
    out = {sp[0]: 0.0 for sp in inner}
    marks = sorted([(s, 1, i) for i, (_, s, d) in enumerate(inner)]
                   + [(s + d, 0, i) for i, (_, s, d) in enumerate(inner)])
    open_, k = set(), 0

    def advance(t):
        nonlocal k
        while k < len(marks) and marks[k][0] <= t:
            _, is_start, i = marks[k]
            (open_.add if is_start else open_.discard)(i)
            k += 1

    for s, e in _gaps(events, t0, t1):
        cur = s
        advance(cur)
        while cur < e:
            nxt = min(e, marks[k][0]) if k < len(marks) else e
            label = (inner[min(open_, key=lambda i: inner[i][2])][0]
                     if open_ else "none")
            out[label] = out.get(label, 0.0) + (nxt - cur) * 1e-9
            cur = nxt
            advance(cur)
    return out


def idle_gaps(events, spans, t0, t1, k=10) -> list:
    """The ``k`` longest idle gaps in [t0, t1], each named by the innermost
    host span (other than the window) open at the gap's middle."""
    gaps = _gaps(events, t0, t1)
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        open_ = [sp for sp in inner if sp[1] <= mid < sp[1] + sp[2]]
        label = min(open_, key=lambda sp: sp[2])[0] if open_ else "none"
        named.append([label, (e - s) * 1e-9])
    return named


def reduce(trace: dict, used: list) -> dict:
    """Per-device busy and collective seconds inside the window, on the
    devices ``used`` (plane names), with the busiest device's module
    seconds, idle seconds by host span and breakdown."""
    t0, t1 = window(trace)
    per = {}
    for dev in used:
        evs = ops(trace, dev)
        both = evs + trace[dev].get(ASYNC_LINE, [])
        per[dev] = {"busy_s": busy_ns(evs, t0, t1) * 1e-9,
                    "collective_s": collective_ns(both, t0, t1) * 1e-9}
    busiest = max(used, key=lambda d: per[d]["busy_s"])
    evs, spans = ops(trace, busiest), host_spans(trace)
    return {"window_s": (t1 - t0) * 1e-9, "per_device": per,
            "busiest": busiest,
            "modules": module_s(trace[busiest].get(MODULE_LINE, []), t0, t1),
            "idle_by_span": idle_by_span(evs, spans, t0, t1),
            "device_ops": top_ops(evs, t0, t1),
            "idle_gaps": idle_gaps(evs, spans, t0, t1)}
