"""What decides ``correct``: the program passes the limit, the bf16
control fails it, and a run whose timed path is broken underneath reads
``correct: false`` for each fault a cell can have.

Runs the harness past its look for a chip, on the CPU, at a size a test
run holds; the four-chip cell runs in a child process with four virtual
CPU devices.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
SRC = os.path.join(BENCH_DIR, "..", "..", "src")
sys.path[:0] = [BENCH_DIR, SRC]

import harness  # noqa: E402
import spec  # noqa: E402

SMALL = {"graph500_s21_spmv": {"scale": 10},
         "graph500_s23_spmv": {"scale": 10},
         "ogbn_arxiv_gcn_spmm": {"scale": 10, "nodes": 700, "edges": 3000}}
ONE_CHIP = ["graph500_s21_spmv_nnz", "arxiv_gcn_spmm_rows",
            "arxiv_gcn_spmm_nnz"]
SEED = 2**31 + 77


def small_cell(name):
    cell = spec.cell(spec.load(), name)
    cell["config"].update(SMALL[cell["workload"]["config"]])
    return cell


def run_small(name, seed=SEED):
    from repro.core import clear_lowering_caches
    clear_lowering_caches()
    return harness.run_cell(small_cell(name), seed, 0.3, False,
                            time.perf_counter(), None)


@pytest.mark.parametrize("config", sorted(SMALL))
def test_program_passes_and_bf16_control_fails_the_limit(config):
    cfg = dict(spec.config(spec.load(), config), **SMALL[config])
    stmt_mod = spec.module("reference", cfg["reference"])
    limit = float(cfg["err_limit"])
    for seed in (1, 2, 3):
        B = spec.module("data", cfg["generator"]).generate(cfg, seed)
        dense = stmt_mod.operands(cfg, B, 1, seed)[0]
        ref = stmt_mod.Reference(B)
        # float32 accumulation of the exact float32 products
        got = (stmt_mod.scipy_csr(B, np.float32) @ dense).astype(np.float32)
        assert ref.error(got, dense) < limit / 3
        assert ref.control_error(dense) > 3 * limit


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name):
    r = run_small(name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    assert list(r)[-1] == "checks"


def _patch_leaves(monkeypatch, wrap):
    from repro.kernels import ref as K
    for leaf in ("leaf_spmv_nnz", "leaf_spmm_rows", "leaf_spmm_nnz"):
        monkeypatch.setattr(K, leaf, wrap(getattr(K, leaf)))


def half_left_out(leaf):
    import jax.numpy as jnp

    def broken(*args):
        args = list(args)
        vals = args[2]
        keep = jnp.arange(vals.shape[0]) < vals.shape[0] // 2
        args[2] = jnp.where(keep, vals, 0)
        return leaf(*args)
    return broken


def answer_altered(leaf):
    def broken(*args):
        return leaf(*args).at[0].add(1.0)
    return broken


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    if fault == "state_unchanged":
        # the step keeps the kernel it had: the new operand never binds
        from repro.core import lower as lower_mod
        monkeypatch.setattr(lower_mod, "rebind_dense", lambda k, m: k)
    else:
        _patch_leaves(monkeypatch, {"half_left_out": half_left_out,
                                    "answer_altered": answer_altered}[fault])
    r = run_small(name)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


CHILD = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}]
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp
import test_spbench_correctness as t
fault = {fault!r}
if fault == "no_exchange":
    orig = jax.lax.psum
    jax.lax.psum = lambda x, axis_name: orig(
        jnp.where(jax.lax.axis_index(axis_name) == 0, x, 0), axis_name)
elif fault != "sound":
    from repro.kernels import ref as K
    wrap = getattr(t, fault)
    K.leaf_spmv_nnz = wrap(K.leaf_spmv_nnz)
r = t.run_small("graph500_s23_spmv_nnz_4chip")
print(json.dumps(r))
"""


@pytest.mark.parametrize("fault", ["sound", "no_exchange", "half_left_out",
                                   "answer_altered"])
def test_four_chip_cell_faults(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=BENCH_DIR, src=SRC, tests=HERE, fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is (fault == "sound"), r["checks"]
