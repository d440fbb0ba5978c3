"""Cost-model-driven autoscheduler (ISSUE 6): the model's structural
decisions (skewed rows → nnz split, uniform rows → universe split), the
tuned-plan cache (warm re-lower skips the search, in-place mutation
re-searches), tile threading for blocked operands, and an
auto-vs-interpreter sweep over every conformance expression × format
family."""
import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import conformance
import repro.core as rc
from repro.core import formats as F
from repro.core import plan_search as PS
from repro.core.interp import interpret
from repro.core.lower import clear_lowering_caches, lower
from repro.core.tensor import Tensor

M4 = rc.Machine(("x", 4))
MODEL_ONLY = PS.SearchConfig(refine_top_k=0)


@pytest.fixture(autouse=True)
def _model_only_auto(monkeypatch):
    """Rank by the cost model alone in tests: on-device refinement on a
    shared CI box is timing noise, and cold-lowering the top-K candidates
    of every sweep cell would dominate the suite's runtime."""
    monkeypatch.setattr(PS, "DEFAULT_CONFIG", MODEL_ONLY)
    clear_lowering_caches()
    yield


# ---------------------------------------------------------------------------
# Structural inputs with a KNOWN right answer. Tall-skinny shapes keep the
# replicated co-operand small, so the ranking is decided by the structural
# terms under test (window imbalance vs the nnz scatter-merge penalty),
# not by communication volume.
# ---------------------------------------------------------------------------

def _spmv(B: Tensor):
    rng = np.random.default_rng(0)
    c = Tensor.from_dense(
        "c", rng.standard_normal(B.shape[1]).astype(np.float32))
    return rc.parse_tin("a(i) = B(i,j) * c(j)",
                        a=Tensor.zeros_dense("a", (B.shape[0],)), B=B, c=c)


def _skewed_csr(n=1000, m=100, heavy=100) -> Tensor:
    """First ``heavy`` rows fully dense, the rest one entry each: a
    row-degree head that every contiguous row window P puts on one piece."""
    rows = np.concatenate([np.repeat(np.arange(heavy), m),
                           np.arange(heavy, n)])
    cols = np.concatenate([np.tile(np.arange(m), heavy),
                           np.arange(n - heavy) % m])
    coords = np.stack([rows, cols], axis=1)
    vals = np.random.default_rng(2).standard_normal(
        rows.size).astype(np.float32)
    return Tensor.from_coo("B", (n, m), coords, vals, F.CSR())


def _uniform_csr(n=1000, m=100, deg=8) -> Tensor:
    """Exactly ``deg`` entries in every row: row windows are perfectly
    balanced, so the nnz split's output-merge penalty is pure overhead."""
    rows = np.repeat(np.arange(n), deg)
    cols = (np.tile(np.arange(deg), n) * (m // deg)) % m
    coords = np.stack([rows, cols], axis=1)
    vals = np.random.default_rng(3).standard_normal(
        rows.size).astype(np.float32)
    return Tensor.from_coo("B", (n, m), coords, vals, F.CSR())


@settings(max_examples=8, deadline=None)
@given(heavy=st.integers(40, 160))
def test_model_picks_nnz_on_skewed_rows(heavy):
    """Skewed row degrees: the padded max window makes every universe
    split (1-D and 2-D) memory-bound on the heavy piece; the balanced nnz
    split wins despite its output-merge penalty."""
    stmt = _spmv(_skewed_csr(heavy=heavy))
    w = PS.search(stmt, M4, config=MODEL_ONLY)
    assert w.space == "nnz" and w.grid == (4, 1)


@settings(max_examples=8, deadline=None)
@given(deg=st.integers(3, 16))
def test_model_picks_rows_on_uniform(deg):
    """Uniform row degrees: windows are balanced, so the nnz split's
    extra pass over the global output is pure loss — rows wins."""
    stmt = _spmv(_uniform_csr(deg=deg))
    w = PS.search(stmt, M4, config=MODEL_ONLY)
    assert w.space == "universe"


def test_estimates_rank_both_regimes():
    """The same model orders the full candidate list, not just the
    winner: nnz beats every universe point on skew and loses to the flat
    rows split on uniform."""
    skew = _spmv(_skewed_csr())
    stats = PS.structural_stats(skew)
    pts = PS.enumerate_points(skew, M4, stats)
    costs = {p.label: PS.estimate(skew, p, stats) for p in pts}
    assert costs["nnz/4x1"] < min(c for l, c in costs.items() if l != "nnz/4x1")
    uni = _spmv(_uniform_csr())
    stats = PS.structural_stats(uni)
    pts = PS.enumerate_points(uni, M4, stats)
    costs = {p.label: PS.estimate(uni, p, stats) for p in pts}
    assert costs["rows/4x1"] < costs["nnz/4x1"]


# ---------------------------------------------------------------------------
# The tuned-plan cache (mirrors test_replan_cache.py's plan-cache pins)
# ---------------------------------------------------------------------------

def _small_spmv(fm, seed=11):
    rng = np.random.default_rng(seed)
    d = ((rng.random((19, 13)) < 0.3) *
         rng.standard_normal((19, 13))).astype(np.float32)
    d[3] = 0                                                    # empty row
    B = Tensor.from_dense("B", d, fm)
    c = Tensor.from_dense("c", rng.standard_normal(13).astype(np.float32))
    return rc.parse_tin("a(i) = B(i,j) * c(j)",
                        a=Tensor.zeros_dense("a", (19,)), B=B, c=c)


def test_auto_cold_then_warm_skips_search(monkeypatch):
    """Cold lower(schedule="auto") searches (tuned_misses); the unchanged
    re-lower serves the memoized point WITHOUT calling search — pinned by
    making a second search a test failure."""
    stmt = _small_spmv(F.CSR())
    k1 = lower(stmt, M4, schedule="auto")
    assert k1.tuned is not None
    assert k1.cache.tuned_misses == 1 and k1.cache.tuned_hits == 0
    assert not k1.cache.warm
    np.testing.assert_allclose(k1.run(), interpret(stmt), atol=1e-3)
    monkeypatch.setattr(
        PS, "search",
        lambda *a, **kw: pytest.fail("warm re-lower must skip the search"))
    k2 = lower(stmt, M4, schedule="auto")
    assert k2.cache.tuned_hits == 1 and k2.cache.tuned_misses == 0
    assert k2.cache.warm
    assert k2.tuned is k1.tuned          # the memoized point itself
    np.testing.assert_allclose(k2.run(), k1.run(), atol=1e-5)


def test_auto_invalidates_on_inplace_mutation():
    """In-place mutation of vals changes the content fingerprint in the
    tuned key: the re-lower re-searches instead of serving a stale winner
    (mirror of test_invalidation_inplace_mutation)."""
    stmt = _small_spmv(F.CSR())
    B = stmt.rhs.accesses()[0].tensor
    k1 = lower(stmt, M4, schedule="auto")
    r1 = k1.run()
    B.vals[:] = B.vals * 5.0
    k2 = lower(stmt, M4, schedule="auto")
    assert k2.cache.tuned_misses == 1 and not k2.cache.warm
    np.testing.assert_allclose(k2.run(), 5.0 * np.asarray(r1), atol=1e-3)


def test_auto_blocked_operand_carries_tuned_tile():
    """Blocked formats: the winning point carries the autotuned Pallas
    (block_R, block_nb) group shape and the built schedule threads it to
    the strategy (what the ops-layer emitters consume)."""
    stmt = _small_spmv(F.BCSR((2, 2)))
    k = lower(stmt, M4, schedule="auto")
    assert k.tuned is not None and k.tuned.tile is not None
    assert k.strategy.tile == k.tuned.tile
    np.testing.assert_allclose(k.run(), interpret(stmt), atol=1e-3)


def test_auto_unknown_string_rejected():
    stmt = _small_spmv(F.CSR())
    with pytest.raises(ValueError, match="unknown schedule string"):
        lower(stmt, M4, schedule="fast")


def test_tuned_cache_capacity_bound():
    """The tuned-plan cache is a bounded LRU like every other cache."""
    old = PS._TUNED_PLAN_CACHE.capacity
    try:
        PS.set_tuned_plan_cache_capacity(1)
        ev0 = PS.TUNED_PLAN_CACHE_STATS["evictions"]
        for seed in (11, 12, 13):
            lower(_small_spmv(F.CSR(), seed=seed), M4, schedule="auto")
        assert len(PS._TUNED_PLAN_CACHE) <= 1
        assert PS.TUNED_PLAN_CACHE_STATS["evictions"] > ev0
    finally:
        PS.set_tuned_plan_cache_capacity(old)


# ---------------------------------------------------------------------------
# Auto × the conformance matrix: every expression × format family must
# lower through schedule="auto" and match the interpreter oracle.
# ---------------------------------------------------------------------------

def _check_auto_cell(expr, fmt_name, fmt_ctor):
    rng = np.random.default_rng(
        zlib.crc32(f"auto/{expr}/{fmt_name}".encode()))
    stmt = conformance._build_stmt(expr, fmt_ctor(), rng)
    clear_lowering_caches()
    k = lower(stmt, M4, schedule="auto")
    assert k.tuned is not None, f"auto cell {expr}/{fmt_name} unplanned"
    result = k.run()
    got = result.to_dense() if isinstance(result, Tensor) else result
    np.testing.assert_allclose(got, interpret(stmt), atol=1e-3,
                               err_msg=f"auto cell {k.cell_id()}")


@pytest.mark.parametrize("fmt_name,fmt_ctor", conformance.FORMATS_2D,
                         ids=[f[0] for f in conformance.FORMATS_2D])
@pytest.mark.parametrize("expr", conformance.EXPRESSIONS_2D)
def test_auto_matrix_2d(expr, fmt_name, fmt_ctor):
    _check_auto_cell(expr, fmt_name, fmt_ctor)


@pytest.mark.parametrize("fmt_name,fmt_ctor", conformance.FORMATS_3D,
                         ids=[f[0] for f in conformance.FORMATS_3D])
@pytest.mark.parametrize("expr", conformance.EXPRESSIONS_3D)
def test_auto_matrix_3d(expr, fmt_name, fmt_ctor):
    _check_auto_cell(expr, fmt_name, fmt_ctor)


# ---------------------------------------------------------------------------
# ISSUE 7: replicated candidates + canonical-key dedupe
# ---------------------------------------------------------------------------

def _wide_spmm(n=200, m=200, J=64, density=0.02, seed=0):
    """|A|·Q > |B|: many output columns over a sparse-ish operand — the
    regime where replicating B along z beats every 2-D factorization."""
    rng = np.random.default_rng(seed)
    dB = ((rng.random((n, m)) < density) *
          rng.standard_normal((n, m))).astype(np.float32)
    B = Tensor.from_dense("B", dB, F.CSR())
    C = Tensor.from_dense("C", rng.standard_normal((m, J)).astype(np.float32))
    return rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (n, J)), B=B, C=C), dB, dC_ref(C)


def dC_ref(C):
    return np.asarray(C.to_dense())


def test_enumeration_dedupes_canonical_plans():
    """Degenerate factorizations that coincide with a lower-order plan
    (P×1 grids, z-depth-1 replication) must be enumerated ONCE — refine
    would otherwise time the same executable twice."""
    stmt, _, _ = _wide_spmm()
    M8 = rc.Machine(("x", 8))
    pts = PS.enumerate_points(stmt, M8, PS.structural_stats(stmt))
    keys = [p.plan_key for p in pts]
    assert len(keys) == len(set(keys)), "duplicate canonical plans enumerated"
    labels = {p.label for p in pts}
    # replicated triples present, every depth a genuine replication
    assert any(p.replicated for p in pts)
    for p in pts:
        if p.replicated:
            assert p.grid[2] >= 2
    # the flat candidates keep their pinned labels
    assert {"rows/8x1", "nnz/8x1"} <= labels


def test_replicated_point_label_and_machine():
    p = PS.SchedulePoint("universe", (2, 2, 2), None, replicated=True)
    assert p.label == "rows/2x2x2r"
    m = p.machine_for(rc.Machine(("x", 8)))
    assert [(d.name, d.size) for d in m.dims] == \
        [("x", 2), ("y", 2), ("z", 2)]
    # canonical stripping: a trailing singleton z IS the 2-D plan
    q = PS.SchedulePoint("universe", (4, 2, 1), None)
    assert q.plan_key == PS.SchedulePoint("universe", (4, 2), None).plan_key


def test_auto_picks_replicated_when_bytes_favor_it():
    """Acceptance: on the wide-output SpMM the byte model must rank a
    2.5-D replicated point first and lower(schedule='auto') must run it."""
    stmt, dB, dC = _wide_spmm()
    M8 = rc.Machine(("x", 8))
    winner = PS.search(stmt, M8, config=MODEL_ONLY)
    assert winner is not None and winner.replicated, winner.label
    clear_lowering_caches()
    k = lower(stmt, M8, schedule="auto")
    assert k.tuned is not None and k.tuned.replicated
    assert k.leaf_name == "spmm_grid_rep_rows"
    assert k.strategy.mesh_label.endswith("r")
    np.testing.assert_allclose(np.asarray(k.run()), dB @ dC, atol=1e-3)


def test_auto_still_picks_nnz_on_skewed_rows_with_replication_enabled():
    """The replicated candidates must not mask the structural nnz win:
    a skewed SpMM's row windows stay imbalanced under every universe
    factorization (replicated included)."""
    B = _skewed_csr()
    rng = np.random.default_rng(5)
    C = Tensor.from_dense(
        "C", rng.standard_normal((B.shape[1], 4)).astype(np.float32))
    stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (B.shape[0], 4)), B=B, C=C)
    winner = PS.search(stmt, M4, config=MODEL_ONLY)
    assert winner is not None and winner.space == "nnz", winner.label


def test_failed_measurement_is_recorded_on_the_candidate(monkeypatch):
    """A candidate whose timing raises keeps an infinite measured cost and
    carries the error into the provenance and explain(); the search still
    returns the best candidate that did run."""
    stmt = _spmv(_skewed_csr())
    real_measure = PS._measure

    def flaky(stmt_, point, *args, **kwargs):
        if point.space == "nnz":
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")
        return real_measure(stmt_, point, *args, **kwargs)

    monkeypatch.setattr(PS, "_measure", flaky)
    cfg = PS.SearchConfig(refine_top_k=2, measure_warmup=0, measure_iters=1)
    monkeypatch.setattr(PS, "DEFAULT_CONFIG", cfg)
    k = lower(stmt, M4, schedule="auto")
    failed = [c for c in k.tuned.candidates if c["error"]]
    assert [c["label"] for c in failed] == ["nnz/4x1"]
    assert failed[0]["measured_s"] == float("inf")
    assert "MemoryError: RESOURCE_EXHAUSTED" in failed[0]["error"]
    assert k.tuned.space == "universe"          # the candidate that ran won
    assert "FAILED measure: MemoryError" in k.explain()


def test_peaks_are_keyed_by_device_kind():
    """The planner plans against the v5e entry by name; a kind with no
    published peaks raises instead of borrowing another chip's."""
    from repro.launch import roofline
    assert PS.PLAN_HW is roofline.hardware_model("TPU v5 lite")
    assert PS.PLAN_HW.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.hardware_model("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.roofline_report("", device_kind="TPU v4")
