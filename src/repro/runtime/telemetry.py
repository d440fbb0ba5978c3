"""Telemetry: span tracing, unified metrics, and byte-ledger verification.

One observability layer for the whole lowering/execution pipeline. Three
pieces:

- :class:`Tracer` — a hierarchical span tracer. ``with span("lower.plan",
  sig=...)`` records a timed span nested under whatever span is open on
  the current thread. An enabled span is also a
  ``jax.profiler.TraceAnnotation``: while a profiler session runs
  (``jax.profiler.trace(dir, create_perfetto_trace=True)``) every span
  lands on the trace's ``/host:CPU`` plane, on the device ops' own clock,
  and the trace opens in Perfetto or TensorBoard. The module-global
  :data:`TRACER` starts **disabled**: every instrumentation site in
  ``core.lower`` / ``core.grid`` / ``core.partition`` /
  ``distributed.executor`` / ``runtime.elastic`` then costs one attribute
  read and one branch (the no-op singleton path — bounded by test).

- :class:`MetricsRegistry` — process-wide counters / gauges / histograms
  behind one :meth:`MetricsRegistry.snapshot` API. The snapshot also
  absorbs the pre-existing scattered cache counters (plan / shard /
  runner / convert / add-stream / tuned-plan / spmd-run) with derived hit
  rates, so ``benchmarks/run.py --json`` and ``launch/report.py`` read
  one structure instead of seven module globals.

- :func:`verify_byte_ledger` — the model-vs-ledger cross-check: re-derive
  the communication bytes a kernel *should* have charged from the
  statement + strategy alone (``grid.grid_axis_bytes`` for grids, the
  ``plan_search`` statement-level predictors for 1-D) and compare against
  the ``CommStats`` ledger the lowering actually recorded, per axis.
  Run over the full conformance census, this pins the paper's per-axis
  communication accounting (DISTAL §5) to the implementation.

Span taxonomy (all names dot-namespaced, stable — tests parse them):
``lower`` > ``lower.plan`` / ``lower.materialize`` / ``lower.emit``;
``run`` (one kernel call, attrs ``leaf`` / ``spmd``) > ``run.copy_in``
(host arguments placed on the device and waited on) / ``run.execute``
(the jitted runner through ``block_until_ready``) — the self time of
``run`` is the copy back to the host and the output's host assembly;
``plan_search.search`` > ``plan_search.measure``;
``partition.materialize``; ``execute.spmd.build`` / ``execute.piece``;
``recovery.restore`` / ``recovery.replan`` / ``recovery.rejit``.

Instants: ``jit.compile`` (``fun_name``, ``seconds``) under whatever span
is open when XLA compiles — a compile under ``run`` after warm-up is a
recompile. Counters recorded while tracing: ``run.calls``,
``run.h2d_bytes``, ``run.d2h_bytes``, ``jit.compiles``, ``jit.compile_s``.
"""
from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

__all__ = [
    "Tracer", "MetricsRegistry", "TRACER", "METRICS", "span", "instant",
    "traced_runner", "configure_logging", "verify_byte_ledger",
]


# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------


class _NullSpan:
    """The disabled-tracer span: a shared singleton whose enter/exit/set
    do nothing. ``Tracer.span`` returns it without allocating when
    tracing is off, so instrumentation sites cost one branch."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span. Created only on the enabled path; records itself
    into the owning tracer's event list on exit, and is open on the
    profiler's trace (a ``TraceAnnotation``) for as long as it lives."""

    __slots__ = ("_tracer", "name", "id", "parent", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.id = 0
        self.parent: Optional[int] = None
        self._t0 = 0.0
        self._ann = jax.profiler.TraceAnnotation(name)

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered after the span opened (e.g. the
        chosen leaf name, a cache-delta)."""
        self.args.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        tr = self._tracer
        stack = tr._stack()
        self.parent = stack[-1].id if stack else None
        with tr._lock:
            tr._seq += 1
            self.id = tr._seq
        stack.append(self)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        tr = self._tracer
        stack = tr._stack()
        if stack and stack[-1] is self:
            stack.pop()
        tr._record({
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "ts_us": (self._t0 - tr._epoch) * 1e6,
            "dur_us": (t1 - self._t0) * 1e6,
            "tid": threading.get_ident(),
            "args": self.args,
        })
        return False


class Tracer:
    """Thread-safe hierarchical span tracer.

    Parentage is tracked per thread (a thread-local span stack) and
    recorded by span *id* at open time — a parent span finishes after its
    children, so positional references cannot work. Disabled tracers
    return the shared no-op span from :meth:`span` and record nothing.
    """

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._local = threading.local()
        self._events: List[Dict[str, Any]] = []
        self._seq = 0
        self._epoch = time.perf_counter()

    # -- control ----------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._seq = 0
            self._epoch = time.perf_counter()

    # -- recording --------------------------------------------------------
    def _stack(self) -> List[_Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _record(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, **attrs):
        """Open a timed span: ``with tracer.span("lower.plan", sig=s):``.
        Returns the no-op singleton when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """A zero-duration marker event (cache hit/miss, fault, …)."""
        if not self.enabled:
            return
        stack = self._stack()
        self._record({
            "name": name,
            "id": None,
            "parent": stack[-1].id if stack else None,
            "ts_us": (time.perf_counter() - self._epoch) * 1e6,
            "dur_us": None,
            "tid": threading.get_ident(),
            "args": attrs,
        })

    # -- inspection -------------------------------------------------------
    def spans(self) -> List[Dict[str, Any]]:
        """Finished events, oldest first (instants have ``dur_us=None``)."""
        with self._lock:
            return list(self._events)

    def call_tree(self) -> List[Dict[str, Any]]:
        """Reconstruct span nesting from recorded parent ids: a forest of
        ``{"name", "dur_us", "args", "children": [...]}`` nodes."""
        nodes: Dict[int, Dict[str, Any]] = {}
        roots: List[Dict[str, Any]] = []
        spans = [e for e in self.spans() if e["id"] is not None]
        for ev in spans:
            nodes[ev["id"]] = {"name": ev["name"], "dur_us": ev["dur_us"],
                               "args": ev["args"], "children": []}
        for ev in spans:
            node = nodes[ev["id"]]
            parent = nodes.get(ev["parent"]) if ev["parent"] else None
            (parent["children"] if parent else roots).append(node)
        for n in nodes.values():
            n["children"].sort(key=lambda c: c["dur_us"] or 0, reverse=True)
        return roots


#: The process-wide tracer every instrumentation site records into.
#: Disabled by default — ``TRACER.enable()`` to start collecting.
TRACER = Tracer(enabled=False)


def span(name: str, **attrs):
    """Module-level convenience: a span on the global :data:`TRACER`."""
    return TRACER.span(name, **attrs)


def instant(name: str, **attrs) -> None:
    """Module-level convenience: an instant on the global :data:`TRACER`."""
    TRACER.instant(name, **attrs)


def overlap_report(tracer: "Tracer" = None) -> Dict[str, Any]:
    """Roll up comm/compute-overlap attribution from recorded spans.

    The overlapped executor (``distributed.executor.run_overlapped``)
    emits one ``execute.overlap.chunk`` instant per dense-operand chunk
    with ``comm_s`` (issue→ready transfer wall time), ``hidden_s`` (the
    slice of that window spent under the previous chunk's compute), and
    ``bytes``. This derives the serving dashboard's summary:
    ``efficiency = sum(hidden_s) / sum(comm_s)`` — the fraction of
    transfer time the pipeline hid behind leaf kernels (0.0 when nothing
    overlapped or tracing was disabled)."""
    tracer = tracer or TRACER
    chunks = [e for e in tracer.spans()
              if e["name"] == "execute.overlap.chunk"]
    comm_s = sum(float(e["args"].get("comm_s", 0.0)) for e in chunks)
    hidden_s = sum(float(e["args"].get("hidden_s", 0.0)) for e in chunks)
    nbytes = sum(int(e["args"].get("bytes", 0)) for e in chunks)
    return {
        "chunks": len(chunks),
        "comm_s": comm_s,
        "hidden_s": hidden_s,
        "bytes": nbytes,
        "efficiency": (hidden_s / comm_s) if comm_s > 0 else 0.0,
    }


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

#: (snapshot key, module, attribute) for every pre-existing cache-stats
#: dict. Read through sys.modules so the registry never forces an import
#: (and never creates a cycle — telemetry is imported BY these modules).
_CACHE_SOURCES: Tuple[Tuple[str, str, str], ...] = (
    ("plan", "repro.core.lower", "PLAN_CACHE_STATS"),
    ("runner", "repro.core.lower", "RUNNER_CACHE_STATS"),
    ("shard", "repro.core.partition", "SHARD_CACHE_STATS"),
    ("convert", "repro.core.partition", "CONVERT_CACHE_STATS"),
    ("add_stream", "repro.core.partition", "ADD_STREAM_STATS"),
    ("tuned_plan", "repro.core.plan_search", "TUNED_PLAN_CACHE_STATS"),
    ("spmd_run", "repro.distributed.executor", "SPMD_RUN_STATS"),
)


class MetricsRegistry:
    """Counters, gauges, and histograms behind one lock and one
    :meth:`snapshot`. Histogram observations are kept raw (bounded use:
    per-piece timings, per-axis bytes) and summarized at snapshot time."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, List[float]] = {}

    def counter(self, name: str, inc: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + inc

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._hists.setdefault(name, []).append(float(value))

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    @staticmethod
    def cache_stats() -> Dict[str, Dict[str, Any]]:
        """Hit/miss (+ derived hit rate) for every registered cache whose
        module is already imported."""
        out: Dict[str, Dict[str, Any]] = {}
        for key, mod_name, attr in _CACHE_SOURCES:
            mod = sys.modules.get(mod_name)
            stats = getattr(mod, attr, None) if mod else None
            if not isinstance(stats, dict):
                continue
            h, m = int(stats.get("hits", 0)), int(stats.get("misses", 0))
            entry: Dict[str, Any] = {"hits": h, "misses": m,
                                     "hit_rate": h / (h + m) if h + m else
                                     None}
            if "evictions" in stats:
                entry["evictions"] = int(stats["evictions"])
            out[key] = entry
        return out

    def snapshot(self) -> Dict[str, Any]:
        """One JSON-ready structure: counters, gauges, histogram
        summaries (count/min/max/mean/p50/p90/total), cache hit rates."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: list(v) for k, v in self._hists.items()}
        summaries = {}
        for name, vals in hists.items():
            a = np.asarray(vals, dtype=np.float64)
            summaries[name] = {
                "count": int(a.size),
                "min": float(a.min()),
                "max": float(a.max()),
                "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p90": float(np.percentile(a, 90)),
                "total": float(a.sum()),
            }
        return {"counters": counters, "gauges": gauges,
                "histograms": summaries, "caches": self.cache_stats()}


#: The process-wide registry every instrumentation site records into.
METRICS = MetricsRegistry()


# ---------------------------------------------------------------------------
# Compile events and the runner boundary
# ---------------------------------------------------------------------------

#: jax.monitoring event of one XLA backend compile (its ``fun_name`` is the
#: jitted function's name: the runners carry their leaf's name).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event_duration(event: str, seconds: float, **attrs) -> None:
    if event != _COMPILE_EVENT or not TRACER.enabled:
        return
    TRACER.instant("jit.compile", fun_name=attrs.get("fun_name"),
                   seconds=seconds)
    METRICS.counter("jit.compiles")
    METRICS.counter("jit.compile_s", seconds)


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def traced_runner(f: Callable) -> Callable:
    """Wrap a jitted runner in the kernel-call boundary that
    ``core.lower._runner`` and ``distributed.executor._spmd_runner`` hand
    out. Disabled, a call is ``f(*args)`` behind one branch. Enabled:

    - ``run.copy_in``: the host-resident (numpy) argument leaves are placed
      on the device and waited on; counter ``run.h2d_bytes``;
    - ``run.execute``: ``f`` through ``block_until_ready``; counters
      ``run.d2h_bytes`` (every caller copies all outputs to the host) and
      ``run.calls``.

    A kernel cannot start before its inputs land, and the caller's
    ``np.asarray`` waits for the result anyway, so tracing changes the
    device timeline by one host wait per call."""

    def call(*args):
        if not TRACER.enabled:
            return f(*args)
        leaves, tree = jax.tree_util.tree_flatten(args)
        host = [i for i, x in enumerate(leaves) if isinstance(x, np.ndarray)]
        with TRACER.span("run.copy_in", arrays=len(host)) as sp:
            placed = jax.block_until_ready(
                jax.device_put([leaves[i] for i in host]))
            h2d = sum(int(x.nbytes) for x in placed)
            sp.set(bytes=h2d)
        for i, x in zip(host, placed):
            leaves[i] = x
        with TRACER.span("run.execute"):
            out = jax.block_until_ready(
                f(*jax.tree_util.tree_unflatten(tree, leaves)))
        METRICS.counter("run.calls")
        METRICS.counter("run.h2d_bytes", h2d)
        METRICS.counter("run.d2h_bytes", sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(out)))
        return out

    return call


def configure_logging(level: int = logging.INFO) -> logging.Logger:
    """Configure the ``repro`` logger hierarchy in one call. Every module
    logs under ``__name__`` (``repro.core.lower``, …), so a level + a
    handler on the ``repro`` root covers the whole package. Idempotent —
    an existing handler is kept, only the level changes."""
    root = logging.getLogger("repro")
    root.setLevel(level)
    if not root.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)-7s %(name)s: %(message)s"))
        root.addHandler(h)
    return root


# ---------------------------------------------------------------------------
# Byte-ledger verification
# ---------------------------------------------------------------------------


def _flat_predicted_bytes(kernel) -> Tuple[int, int]:
    """(replicate, reduce) bytes a 1-D (or per-color grid-nnz) lowering of
    ``kernel.stmt`` must charge, re-derived from the statement + plans —
    independent of the running totals ``_lower_impl`` accumulated."""
    from ..core import lower as L
    from ..core import plan_search as PS

    stmt, strat = kernel.stmt, kernel.strategy
    sig = stmt.signature()

    if (sig, strat.space) in L._SELF_MATERIALIZING:
        # spadd3/nnz: whole concatenated entry stream ships to the root —
        # coords+vals per scalar entry, coords + a (br, bc) tile per block.
        seen, n_entries, tile = set(), 0, 0
        for acc in stmt.rhs.accesses():
            t = acc.tensor
            if t.format.is_sparse and t.name not in seen:
                seen.add(t.name)
                n_entries += int(t.vals.shape[0])
                if t.format.is_blocked:
                    tile = int(np.prod(t.format.block_shape))
        red = n_entries * (8 + tile * 4) if tile else n_entries * 12
        return 0, red

    if strat.space == "universe":
        rep = sum(L._nbytes(t) for t in PS._replicated_universe(stmt))
        return int(rep), 0

    # nnz space: operands replicate, output partials reduce
    rep_ts, out_partitioned = PS._replicated_nnz(stmt)
    rep = sum(L._nbytes(t) for t in rep_ts)
    out_t = stmt.lhs.tensor
    if not out_partitioned and not L._output_is_assembled(sig):
        # _compute_plans replicates the dense output when its leading
        # variable is not the position tensor's root variable (CSC/BCSC)
        rep += L._nbytes(out_t)
    ov = kernel.plans[next(iter(kernel.plans))]   # position-tensor plan
    if ov.tensor.format.dim_of_level(0) != 0:
        red = L._nbytes(out_t)                    # full-extent partials
    elif ov.tensor.format.is_blocked:
        bb = ov.levels[0].coord_bounds
        br = ov.tensor.format.block_shape[0]
        red = int((bb[:, 1] - bb[:, 0]).sum()
                  - (bb[:, 1].max() - bb[:, 0].min())) * br * 4
    else:
        rb = ov.root_coord_bounds
        red = int((rb[:, 1] - rb[:, 0]).sum()
                  - (rb[:, 1].max() - rb[:, 0].min())) * 4
    return int(rep), int(red)


def verify_byte_ledger(kernel) -> Dict[str, Any]:
    """Cross-check the kernel's recorded :class:`~repro.core.lower.
    CommStats` ledger against statement-level model predictions, per
    machine axis. Covers replicate/broadcast and reduce bytes (the model
    has no view of ``redistribute_bytes`` — a property of the *data*
    distribution, not the schedule). Raises ``AssertionError`` on any
    mismatch; returns the check report."""
    from ..core import grid as grid_mod
    from ..core import lower as L  # noqa: F401 — force module availability

    stmt, strat, comm = kernel.stmt, kernel.strategy, kernel.comm
    checks: List[Dict[str, Any]] = []

    def chk(field: str, axis: Optional[str], pred: int, ledger: int) -> None:
        checks.append({"field": field, "axis": axis, "predicted": int(pred),
                       "ledger": int(ledger), "ok": int(pred) == int(ledger)})

    if strat.is_grid and strat.space == "universe":
        model = grid_mod.grid_axis_bytes(stmt, strat)
        assert set(model) == set(comm.axes), (
            f"axis sets differ: model {sorted(model)} "
            f"vs ledger {sorted(comm.axes)}")
        for name in model:
            chk("broadcast", name, model[name].broadcast_bytes,
                comm.axes[name].broadcast_bytes)
            chk("reduce", name, model[name].reduce_bytes,
                comm.axes[name].reduce_bytes)
    elif strat.is_grid:
        # grid nnz: flat prediction re-attributed hierarchically in grid
        # order — the same collective model _lower_impl applies.
        rep, red = _flat_predicted_bytes(kernel)
        m = 1
        for d in strat.machine_dims:
            ax = comm.axes[d.name]
            chk("broadcast", d.name, m * rep, ax.broadcast_bytes)
            chk("reduce", d.name, m * red, ax.reduce_bytes)
            m *= d.size
    else:
        rep, red = _flat_predicted_bytes(kernel)
        chk("replicate", None, rep, comm.replicate_bytes)
        chk("reduce", None, red, comm.reduce_bytes)

    report = {"cell": kernel.cell_id(), "checks": checks,
              "ok": all(c["ok"] for c in checks)}
    bad = [c for c in checks if not c["ok"]]
    assert not bad, (
        f"byte-ledger mismatch for {kernel.cell_id()}: " + "; ".join(
            f"{c['field']}" + (f"[{c['axis']}]" if c["axis"] else "")
            + f" predicted={c['predicted']} ledger={c['ledger']}"
            for c in bad))
    return report
