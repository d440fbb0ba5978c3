"""run.py refuses the CPU and a directory without the program: a non-zero
exit and no result line."""
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
ARGS = ["--workload", "arxiv_gcn_spmm_nnz", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, run_py):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, run_py] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not any(
        ln.lstrip().startswith("{") for ln in lines)


def test_cpu_backend_is_refused_with_no_result_line():
    proc = _run(ROOT, os.path.join(BENCH_DIR, "run.py"))
    assert _no_result(proc), (proc.returncode, proc.stdout)
    assert "refused" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    dst = tmp_path / "benchmarks" / "spbench"
    shutil.copytree(BENCH_DIR, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, str(dst / "run.py"))
    assert _no_result(proc), (proc.returncode, proc.stdout)
