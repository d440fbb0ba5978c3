"""Telemetry subsystem: hierarchical span tracer on the profiler's
clock, the kernel-call boundary (copy-in / execute spans, transfer byte
counters, compile events), metrics registry snapshot, byte-ledger
verification, per-piece kernel profiling -> weighted re-plan, explain()
provenance, and the span-derived RecoveryReport time-split invariant (the
double-count bugfix regression)."""
import glob
import logging
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax

import numpy as np
import pytest

import repro.core as rc
from repro.core import formats as F
from repro.core.interp import interpret
from repro.core.lower import (LoweredKernel, clear_lowering_caches,
                              default_grid_schedule, default_nnz_schedule,
                              default_row_schedule, lower, rebind_dense,
                              relower)
from repro.core.tensor import Tensor
from repro.distributed.executor import profile_pieces
from repro.launch.report import telemetry_table
from repro.runtime import telemetry
from repro.runtime.elastic import run_with_recovery
from repro.runtime.fault import FaultEvent, FaultInjector, StragglerMitigator

M4 = rc.Machine(("x", 4))
M22 = rc.Machine(("x", 2), ("y", 2))


def _sparse(rng, n, m, density=0.25, ints=False):
    mask = rng.random((n, m)) < density
    v = (rng.integers(-3, 4, (n, m)).astype(np.float32) if ints
         else rng.standard_normal((n, m)).astype(np.float32))
    d = (mask * v).astype(np.float32)
    d[rng.integers(0, n)] = 0                                   # empty row
    return d


def _spmv(fm=None, n=19, m=13, seed=1):
    fm = fm if fm is not None else F.CSR()
    rng = np.random.default_rng(seed)
    B = Tensor.from_dense("B", _sparse(rng, n, m), fm)
    c = Tensor.from_dense("c", rng.standard_normal(m).astype(np.float32))
    return rc.parse_tin("a(i) = B(i,j) * c(j)",
                        a=Tensor.zeros_dense("a", (n,)), B=B, c=c)


def _spmm(n=48, m=40, j=8, seed=2, fm=None):
    rng = np.random.default_rng(seed)
    B = Tensor.from_dense("B", _sparse(rng, n, m),
                          fm if fm is not None else F.CSR())
    C = Tensor.from_dense("C", rng.standard_normal((m, j)).astype(np.float32))
    return rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (n, j)), B=B, C=C)


# ---------------------------------------------------------------------------
# Tracer core: nesting, threads, the profiler's trace
# ---------------------------------------------------------------------------

def test_span_nesting_call_tree():
    tr = telemetry.Tracer(enabled=True)
    with tr.span("outer", who="test"):
        with tr.span("inner.a", k=1):
            pass
        with tr.span("inner.b"):
            with tr.span("leaf"):
                pass
        tr.instant("tick", n=7)

    def worker():
        with tr.span("thread.root"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()

    counts = {}
    for ev in tr.spans():
        counts[ev["name"]] = counts.get(ev["name"], 0) + 1
    assert counts == {"outer": 1, "inner.a": 1, "inner.b": 1, "leaf": 1,
                      "tick": 1, "thread.root": 1}

    # call_tree reconstructs the nesting from recorded parent ids
    roots = tr.call_tree()
    names = {r["name"] for r in roots}
    assert names == {"outer", "thread.root"}    # thread gets its own stack
    outer = next(r for r in roots if r["name"] == "outer")
    assert {c["name"] for c in outer["children"]} == {"inner.a", "inner.b"}
    inner_b = next(c for c in outer["children"] if c["name"] == "inner.b")
    assert [c["name"] for c in inner_b["children"]] == ["leaf"]
    assert outer["args"] == {"who": "test"}
    # parent spans strictly contain their children in time
    assert outer["dur_us"] >= inner_b["dur_us"] >= inner_b["children"][0][
        "dur_us"]


def _host_events(trace_dir):
    """(name, start_ns, dur_ns) of every event on the trace's /host:CPU."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    plane = next(p for p in ProfileData.from_file(paths[0]).planes
                 if p.name == "/host:CPU")
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for line in plane.lines for ev in line.events]


def test_enabled_span_lands_on_the_profiler_trace(tmp_path):
    tr = telemetry.Tracer(enabled=True)
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = np.arange(1024, dtype=np.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("probe.outer"):
            with tr.span("probe.inner"):
                f(x).block_until_ready()
        with telemetry.Tracer(enabled=False).span("probe.disabled"):
            pass
    evs = {name: (t0, dur) for name, t0, dur in _host_events(str(tmp_path))}
    assert "probe.outer" in evs and "probe.inner" in evs
    assert "probe.disabled" not in evs
    (o0, od), (i0, idur) = evs["probe.outer"], evs["probe.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od
    # the in-memory record is unchanged by the annotation
    assert [e["name"] for e in tr.spans()] == ["probe.inner", "probe.outer"]


def test_disabled_tracer_is_noop_and_cheap():
    tr = telemetry.Tracer(enabled=False)
    with tr.span("never", big=list(range(100))) as sp:
        sp.set(late=1)
    tr.instant("never.i")
    assert tr.spans() == []
    # the disabled path hands back one shared null object — no allocation
    assert tr.span("a") is tr.span("b")

    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("x", a=1):
            pass
    unit = (time.perf_counter() - t0) / n
    assert unit < 20e-6          # generous bound; typically well under 2us


def test_disabled_tracer_no_measurable_warm_relower_overhead():
    """Acceptance: with the global tracer disabled, the instrumentation
    cost of a warm re-lower is bounded by (spans it WOULD record) x (null
    span unit cost) — and that bound is a small fraction of the re-lower
    wall time itself."""
    stmt = _spmv()
    clear_lowering_caches()
    assert not telemetry.TRACER.enabled
    lower(stmt, M4)                                   # cold: fill caches

    t0 = time.perf_counter()
    k = lower(stmt, M4)                               # warm re-lower
    warm_s = time.perf_counter() - t0
    assert k.cache.warm

    telemetry.TRACER.clear()
    telemetry.TRACER.enable()
    try:
        lower(stmt, M4)
        n_events = len(telemetry.TRACER.spans())
    finally:
        telemetry.TRACER.disable()
        telemetry.TRACER.clear()
    assert n_events > 0

    tr = telemetry.Tracer(enabled=False)
    reps = 5000
    t0 = time.perf_counter()
    for _ in range(reps):
        with tr.span("x", a=1):
            pass
    unit = (time.perf_counter() - t0) / reps
    # every span site costs `unit` when disabled; total ≪ warm lower time
    assert n_events * unit < max(warm_s, 1e-4) * 0.05


def test_disabled_tracer_no_measurable_run_overhead():
    """With the global tracer disabled, run() passes two branches (the
    ``run`` span site and the runner boundary) on its way to the jitted
    runner. Their cost, measured on a no-op runner, is a small fraction of
    the smallest real run()."""
    stmt = _spmv()
    k = lower(stmt, M4, schedule=default_nnz_schedule(stmt, M4))
    assert not telemetry.TRACER.enabled
    k.run()                                           # compile
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        k.run()
    run_s = (time.perf_counter() - t0) / reps

    noop = telemetry.traced_runner(lambda *a: None)
    kern = LoweredKernel(
        stmt=None, strategy=None, machine=None, plans={}, shards={},
        runner=lambda: noop(1, 2, 3), comm=None, leaf_name="noop")
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        kern.run()
    unit = (time.perf_counter() - t0) / reps
    assert unit < 20e-6          # generous bound; typically well under 1us
    assert unit < run_s * 0.05


# ---------------------------------------------------------------------------
# The kernel-call boundary: run / run.copy_in / run.execute, byte counters,
# compile events, runners named by leaf
# ---------------------------------------------------------------------------

@pytest.fixture
def runner_calls(monkeypatch):
    """Every (jitted runner, host arguments) pair called through the
    traced runner boundary, from a cold runner cache."""
    seen = []
    orig = telemetry.traced_runner

    def spy(f):
        def g(*args):
            seen.append((f, args))
            return f(*args)
        return orig(g)

    monkeypatch.setattr(telemetry, "traced_runner", spy)
    clear_lowering_caches()
    telemetry.METRICS.clear()
    telemetry.TRACER.clear()
    telemetry.TRACER.enable()
    yield seen
    telemetry.TRACER.disable()
    telemetry.TRACER.clear()
    telemetry.METRICS.clear()
    clear_lowering_caches()


_BOUNDARY_CASES = [(_spmv, default_row_schedule, "spmv_rows"),
                   (_spmv, default_nnz_schedule, "spmv_nnz"),
                   (_spmm, default_row_schedule, "spmm_rows"),
                   (_spmm, default_nnz_schedule, "spmm_nnz")]


@pytest.mark.parametrize("mk, sched, leaf", _BOUNDARY_CASES)
def test_run_counts_argument_and_output_bytes(runner_calls, mk, sched, leaf):
    """h2d is every host argument byte the compiled runner reads and d2h
    every output byte it returns, by XLA's own memory analysis."""
    stmt = mk()
    k = lower(stmt, M4, schedule=sched(stmt, M4))
    assert k.leaf_name == leaf
    y = k.run()
    np.testing.assert_allclose(y, interpret(stmt), rtol=1e-5, atol=1e-5)
    (f, args), = runner_calls
    mem = f.lower(*args).compile().memory_analysis()
    c = telemetry.METRICS.snapshot()["counters"]
    assert c["run.calls"] == 1
    assert c["run.h2d_bytes"] == mem.argument_size_in_bytes > 0
    assert c["run.d2h_bytes"] == mem.output_size_in_bytes == y.nbytes

    roots = [r for r in telemetry.TRACER.call_tree() if r["name"] == "run"]
    assert len(roots) == 1 and roots[0]["args"] == {"leaf": leaf,
                                                    "spmd": False}
    kids = {c["name"]: c for c in roots[0]["children"]}
    assert set(kids) == {"run.copy_in", "run.execute"}
    assert kids["run.copy_in"]["args"]["bytes"] == c["run.h2d_bytes"]


@pytest.mark.parametrize("mk, sched, leaf", _BOUNDARY_CASES)
def test_runner_module_is_named_by_leaf(runner_calls, mk, sched, leaf):
    stmt = mk()
    lower(stmt, M4, schedule=sched(stmt, M4)).run()
    (f, args), = runner_calls
    assert f.lower(*args).compile().as_text().startswith(
        f"HloModule jit_{leaf}")
    compiles = [e for e in telemetry.TRACER.spans()
                if e["name"] == "jit.compile"]
    assert [e["args"]["fun_name"] for e in compiles] == [f"jit({leaf})"]


def test_rebind_and_run_again_does_not_recompile(runner_calls):
    """The first traced run() compiles once, under run.execute; later
    rebinds with same-shape operands, traced or not, never compile."""
    stmt = _spmm()
    k = lower(stmt, M4, schedule=default_nnz_schedule(stmt, M4))
    k.run()
    events = telemetry.TRACER.spans()
    compiles = [e for e in events if e["name"] == "jit.compile"]
    assert len(compiles) == 1 and compiles[0]["args"]["seconds"] > 0
    execute = next(e for e in events if e["name"] == "run.execute")
    assert compiles[0]["parent"] == execute["id"]
    c = telemetry.METRICS.snapshot()["counters"]
    assert c["jit.compiles"] == 1
    assert c["jit.compile_s"] == pytest.approx(compiles[0]["args"]["seconds"])

    rng = np.random.default_rng(9)
    for traced in (False, True):
        C = Tensor.from_dense("C", rng.standard_normal((40, 8)).astype(
            np.float32))
        k = rebind_dense(k, {"C": C})
        telemetry.TRACER.enabled = traced
        y = k.run()
        np.testing.assert_allclose(y, interpret(k.stmt), rtol=1e-5,
                                   atol=1e-5)
    assert len(runner_calls) == 3
    assert telemetry.METRICS.snapshot()["counters"]["jit.compiles"] == 1
    assert sum(e["name"] == "jit.compile"
               for e in telemetry.TRACER.spans()) == 1
    assert sum(e["name"] == "run" for e in telemetry.TRACER.spans()) == 2


def test_to_spmd_counts_no_copy_in_on_four_devices():
    """Through to_spmd the shards are placed once, so a traced call copies
    nothing in; what it copies out is the replicated psum'd output."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    prog = textwrap.dedent("""
        import numpy as np
        import repro.core as rc
        from repro.core import formats as F
        from repro.core.interp import interpret
        from repro.core.lower import default_nnz_schedule, lower
        from repro.core.tensor import Tensor
        from repro.distributed.executor import to_spmd
        from repro.runtime import telemetry

        rng = np.random.default_rng(3)
        d = (rng.random((60, 50)) < 0.2) * rng.standard_normal((60, 50))
        B = Tensor.from_dense("B", d.astype(np.float32), F.CSR())
        for j in (None, 8):
            if j is None:
                c = Tensor.from_dense("c", rng.standard_normal(50).astype(
                    np.float32))
                stmt = rc.parse_tin("a(i) = B(i,j) * c(j)", B=B, c=c,
                                    a=Tensor.zeros_dense("a", (60,)))
            else:
                C = Tensor.from_dense("C", rng.standard_normal(
                    (50, j)).astype(np.float32))
                stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)", B=B, C=C,
                                    A=Tensor.zeros_dense("A", (60, j)))
            M = rc.Machine(("x", 4))
            k = lower(stmt, M, schedule=default_nnz_schedule(stmt, M))
            call = to_spmd(k)
            assert len(call.placed[0].sharding.mesh.devices.flat) == 4
            telemetry.METRICS.clear()
            telemetry.TRACER.clear()
            telemetry.TRACER.enable()
            y = call()
            telemetry.TRACER.disable()
            np.testing.assert_allclose(y, interpret(stmt), rtol=1e-5,
                                       atol=1e-5)
            c = telemetry.METRICS.snapshot()["counters"]
            assert c["run.calls"] == 1, c
            assert c["run.h2d_bytes"] == 0, c
            assert c["run.d2h_bytes"] == y.nbytes, (c, y.nbytes)
            run, = [r for r in telemetry.TRACER.call_tree()
                    if r["name"] == "run"]
            assert run["args"] == {"leaf": k.leaf_name, "spmd": True}
            assert {ch["name"] for ch in run["children"]} == {
                "run.copy_in", "run.execute"}
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=src,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().endswith("OK")


# ---------------------------------------------------------------------------
# explain(): plan provenance with scored candidates
# ---------------------------------------------------------------------------

def test_explain_lists_scored_candidates():
    stmt = _spmv()
    clear_lowering_caches()
    k = lower(stmt, M4, schedule="auto")
    assert k.tuned is not None and k.tuned.candidates
    assert len(k.tuned.candidates) >= 2
    txt = k.explain()
    assert "autoscheduler winner" in txt and "<- winner" in txt
    for c in k.tuned.candidates:
        assert c["label"] in txt
    # hand-picked schedules say so instead of inventing candidates
    k2 = lower(stmt, M4, schedule=default_row_schedule(stmt, M4))
    assert "hand-picked schedule" in k2.explain()
    assert "comm:" in k2.explain()


# ---------------------------------------------------------------------------
# Byte-ledger verification: model vs recorded CommStats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mk, sched", [
    (lambda: _spmv(F.CSR()), default_row_schedule),       # 1-D rows
    (lambda: _spmv(F.CSC()), default_nnz_schedule),       # output-replicated
    (lambda: _spmm(), default_nnz_schedule),              # 1-D nnz
    (lambda: _spmm(), default_grid_schedule),             # grid universe
], ids=["rows", "csc-nnz", "nnz", "grid"])
def test_byte_ledger_agrees(mk, sched):
    stmt = mk()
    machine = M22 if sched is default_grid_schedule else M4
    clear_lowering_caches()
    k = lower(stmt, machine, schedule=sched(stmt, machine))
    rep = telemetry.verify_byte_ledger(k)
    assert rep["ok"] and rep["checks"]
    np.testing.assert_allclose(k.run(), interpret(stmt), atol=1e-3)


def test_byte_ledger_spadd3_nnz():
    n, m = 24, 20

    def mk(name, seed):
        return Tensor.from_dense(
            name, _sparse(np.random.default_rng(seed), n, m), F.CSR())

    stmt = rc.parse_tin(
        "A(i,j) = B(i,j) + C(i,j) + D(i,j)",
        A=Tensor.zeros_dense("A", (n, m)),
        B=mk("B", 1), C=mk("C", 2), D=mk("D", 3))
    clear_lowering_caches()
    k = lower(stmt, M4, schedule=default_nnz_schedule(stmt, M4))
    rep = telemetry.verify_byte_ledger(k)
    assert rep["ok"]


def test_byte_ledger_catches_tampering():
    stmt = _spmv()
    clear_lowering_caches()
    k = lower(stmt, M4, schedule=default_row_schedule(stmt, M4))
    telemetry.verify_byte_ledger(k)
    k.comm.replicate_bytes += 1
    with pytest.raises(AssertionError, match="byte-ledger mismatch"):
        telemetry.verify_byte_ledger(k)


# ---------------------------------------------------------------------------
# Per-piece kernel profiling -> skew -> weighted re-plan
# ---------------------------------------------------------------------------

def test_profile_pieces_feeds_weighted_replan():
    stmt = _spmm()
    clear_lowering_caches()
    telemetry.METRICS.clear()
    k = lower(stmt, M4, schedule=default_nnz_schedule(stmt, M4))
    ref = np.asarray(k.run())
    prof = profile_pieces(k, iters=2, warmup=1)
    assert prof.leaf_name == k.leaf_name
    assert prof.seconds.shape == (k.strategy.pieces,)
    assert np.all(prof.seconds > 0) and prof.skew() >= 1.0
    w = prof.replan_weights()
    assert w.shape == prof.seconds.shape
    assert abs(w.mean() - 1.0) < 1e-6        # StragglerMitigator convention
    # slower piece -> smaller weight (fewer non-zeros next plan)
    assert np.argmin(w) == np.argmax(prof.seconds)
    k2 = relower(k, M4, weights=w)
    np.testing.assert_allclose(np.asarray(k2.run()), ref, atol=1e-4)
    snap = telemetry.METRICS.snapshot()
    h = snap["histograms"]["executor.piece_seconds"]
    assert h["count"] == k.strategy.pieces       # one best-of obs per piece
    assert snap["gauges"]["executor.piece_skew"] == pytest.approx(
        prof.skew())


def test_profile_pieces_grid_leaf():
    stmt = _spmm()
    clear_lowering_caches()
    k = lower(stmt, M22, schedule=default_grid_schedule(stmt, M22))
    prof = profile_pieces(k, iters=1, warmup=1)
    assert prof.seconds.shape == (k.strategy.pieces,)
    assert not prof.stragglers(threshold=1e9)


# ---------------------------------------------------------------------------
# Metrics registry, snapshot render, logging namespaces
# ---------------------------------------------------------------------------

def test_metrics_snapshot_and_render():
    stmt = _spmv()
    clear_lowering_caches()
    telemetry.METRICS.clear()
    lower(stmt, M4)
    lower(stmt, M4)                          # warm
    snap = telemetry.METRICS.snapshot()
    assert snap["counters"]["lower.count"] == 2
    assert snap["counters"]["lower.warm_count"] >= 1
    assert snap["counters"]["comm.network_bytes"] > 0
    assert snap["caches"]["plan"]["hits"] >= 1
    md = telemetry_table(snap)
    assert "### Caches" in md and "lower.count" in md
    assert telemetry_table({}) == "(empty telemetry snapshot)"
    telemetry.METRICS.clear()
    assert telemetry.METRICS.snapshot()["counters"] == {}


def test_logger_namespaces_and_configure_logging():
    import repro.core.lower as L
    import repro.core.plan_search as PS
    assert L.log.name == "repro.core.lower"        # was "repro.lower"
    assert PS.log.name == "repro.core.plan_search"
    root = telemetry.configure_logging(logging.DEBUG)
    assert root.name == "repro" and root.level == logging.DEBUG
    assert root.handlers
    # idempotent: a second call must not stack handlers
    n = len(root.handlers)
    telemetry.configure_logging(logging.INFO)
    assert len(root.handlers) == n


# ---------------------------------------------------------------------------
# Recovery: span-derived report — splits sum exactly to recovery_s
# (regression for the straggler+device-loss double-count bug)
# ---------------------------------------------------------------------------

def test_recovery_report_splits_sum_exactly(tmp_path_factory):
    rng = np.random.default_rng(9)
    dB = _sparse(rng, 48, 40, ints=True)
    dC = rng.integers(-3, 4, (40, 8)).astype(np.float32)

    def mkstmt():
        B = Tensor.from_dense("B", dB.copy(), F.CSR())
        C = Tensor.from_dense("C", dC.copy())
        return rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                            A=Tensor.zeros_dense("A", (48, 8)), B=B, C=C)

    s0 = mkstmt()
    clear_lowering_caches()
    ref, _ = run_with_recovery(s0, M4, 8,
                               ckpt_dir=str(tmp_path_factory.mktemp("r")),
                               schedule=default_nnz_schedule(s0, M4))

    # straggler re-plans AND a device loss in ONE run: the old hand-timed
    # report double-counted the straggler re-plan that landed in the same
    # recovery window as the device-loss re-plan.
    clear_lowering_caches()
    s1 = mkstmt()
    inj = FaultInjector(
        [FaultEvent(step=s, kind="straggler", piece=2, slowdown_s=0.05)
         for s in (2, 3, 4)]
        + [FaultEvent(step=6, kind="device_loss", piece=1)])
    mit = StragglerMitigator(4, report_budget=2)
    state, rep = run_with_recovery(
        s1, M4, 8, ckpt_dir=str(tmp_path_factory.mktemp("f")),
        schedule=default_nnz_schedule(s1, M4), injector=inj, mitigator=mit)

    assert np.array_equal(state, ref)
    assert rep.replans >= 1 and rep.restarts == 1
    assert rep.recovery_s > 0
    split_sum = rep.restore_s + rep.replan_s + rep.rejit_s
    assert abs(split_sum - rep.recovery_s) < 1e-9   # phases never nest
    # every phase that must have fired shows up in its own bucket
    assert rep.restore_s > 0 and rep.replan_s > 0 and rep.rejit_s > 0
