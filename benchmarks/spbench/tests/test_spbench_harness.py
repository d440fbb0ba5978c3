"""The harness drives whatever a cell's loop builds: a step that lowers two
statements has both kernels logged, is refused when either falls back,
and is checked as every cell is. Readers get the program's counters over
set-up and over a traced window, and nothing compiles in the window.

Runs on the CPU at a size a test run holds.
"""
import dataclasses
import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "..", "..", "src")]

import harness  # noqa: E402
import lowering  # noqa: E402
import spec  # noqa: E402

CELL = "arxiv_gcn_spmm_rows"
SMALL = {"scale": 10, "nodes": 700, "edges": 3000}
SEED = 2**31 + 1234


def small_cell(loop=None):
    cell = spec.cell(spec.load(), CELL)
    cell["config"].update(SMALL)
    if loop is not None:
        cell["loop"] = loop
    return cell


def run(cell, trace=False):
    from repro.core import clear_lowering_caches
    clear_lowering_caches()
    return harness.run_cell(cell, SEED, 0.3, trace, time.perf_counter(),
                            None)


def two_kernel_loop(index=0, **mark):
    """A loop whose step runs the cell's statement lowered twice, under the
    row and the non-zero schedule, and returns the mean of the two
    outputs; ``mark`` replaces fields of kernel ``index`` as lowered."""

    def make(cfg, B, pool, mix, parts):
        kernels = [lowering.lower_statement(cfg, B, pool[0],
                                            dict(mix, schedule=s), parts)
                   for s in ("row", "nnz")]
        if mark:
            kernels[index] = dataclasses.replace(kernels[index], **mark)

        def step(i):
            ya, yb = (k.run() for k in kernels)
            return (ya + yb) / 2, 0, None

        return step, None, kernels

    return types.SimpleNamespace(make=make)


def _logged(out, prefix):
    return [json.loads(ln[len(prefix):]) for ln in out.splitlines()
            if ln.startswith(prefix)]


def test_two_kernel_step_logs_both_and_is_correct(capsys):
    r = run(small_cell(two_kernel_loop()))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    lowered = _logged(capsys.readouterr().out, "lowered ")
    assert [k["leaf"] for k in lowered] == ["spmm_rows", "spmm_nnz"]
    assert all(k["fallbacks"] == [] for k in lowered)


@pytest.mark.parametrize("index", [0, 1])
@pytest.mark.parametrize("mark", [{"fallbacks": ["csr->coo"]},
                                  {"leaf_name": "generic[spmm|nnz]"}])
def test_a_fallback_in_either_kernel_refuses_the_run(index, mark):
    with pytest.raises(RuntimeError, match="did not lower directly"):
        run(small_cell(two_kernel_loop(index, **mark)))


def test_an_altered_second_kernel_is_not_correct(monkeypatch):
    from repro.kernels import ref as K
    leaf = K.leaf_spmm_nnz
    monkeypatch.setattr(K, "leaf_spmm_nnz",
                        lambda *args: leaf(*args).at[0].add(1.0))
    r = run(small_cell(two_kernel_loop()))
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


def test_untraced_window_compiles_nothing_and_leaves_the_tracer_off(capsys):
    from repro.runtime import telemetry
    r = run(small_cell())
    assert r["correct"] and not telemetry.TRACER.enabled
    window, = _logged(capsys.readouterr().out, "window ")
    assert window["compiles"] == 0


def test_traced_run_hands_readers_the_programs_counters():
    """On the CPU the trace has no TPU plane, so only the counter readers
    find something: compile seconds over set-up, and per iteration the
    bytes placed (sparse shards and features) and returned (the
    output)."""
    from repro.runtime import telemetry
    cell = small_cell()
    r = run(cell, trace=True)
    m = r["metrics"]
    assert r["correct"] and not telemetry.TRACER.enabled
    assert m["compile_s"]["value"] > 0
    out_mb = SMALL["nodes"] * cell["config"]["dense_cols"] * 4 / 1e6
    assert m["d2h_mb_per_iter"]["value"] == pytest.approx(out_mb)
    assert m["h2d_mb_per_iter"]["value"] > out_mb
    for name in ("copy_in_idle_ms_per_iter", "copy_out_idle_ms_per_iter",
                 "device_ms_per_iter", "device_idle_share"):
        assert name not in m
