"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (paper §VI mapping):

  bench_vs_interp     — Fig. 10: compiled vs interpretation (C1)
  bench_spadd3        — Fig. 10c: fused vs pairwise adds (C2)
  bench_load_balance  — §II-D: universe vs non-zero partitions (C3)
  bench_mismatch      — §II-D: data vs computation distribution (C4)
  bench_weak_scaling  — Fig. 13: banded SpMV weak scaling
  bench_pallas_kernels— leaf/packing microbench
  bench_bcsr          — direct blocked (BCSR) path vs conversion fallback
  bench_replan        — re-plan fast path: cold lower vs warm re-lower
                        (plan/shard/runner caches) vs execute-only
  bench_mesh2d        — 1-D vs 2-D machine grid at fixed piece count:
                        SpMM comm volume (per-axis) + wall time
  bench_levels        — level-iterator walks: direct csc (transpose walk)
                        & coo3 (trailing-singleton walk) vs the
                        conversion-fallback execution they replaced
  bench_autotune      — autoscheduler: auto-chosen schedule vs best/worst
                        hand-picked cell + cold vs tuned-warm lower time
  bench_replication   — communication-avoiding replication: SpMM comm
                        volume + wall time across 1-D / best 2-D /
                        replicated 2.5-D grids and SpMTTKRP across
                        1-D / P×Q×R bricks, at fixed total pieces
  bench_fault         — elastic recovery: cold P−1 re-lower vs shard-
                        reusing relower(dead=…), plus the recovery wall
                        time split restore / re-plan / re-jit
  bench_serving       — serving fast path: run_many batching vs a
                        per-request loop, p50/p99 latency vs SLO through
                        SparseKernelServer, and double-buffered
                        comm/compute overlap efficiency

Scale flag: ``--quick`` shrinks inputs for CI-speed runs. ``--json`` also
writes a machine-readable ``BENCH_<suite>.json`` per suite to
``--out-dir`` — the perf-trajectory artifacts collected by nightly CI.
Each file is ``{"results": {name: us_per_call}, "telemetry": snapshot}``:
the telemetry snapshot (cache hit rates, per-axis communication byte
counters, per-piece skew when profiled) captures *why* the numbers moved,
not just wall time. Counters/gauges/histograms reset per suite; the cache
stats are process-cumulative.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<suite>.json alongside the CSV")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_*.json files")
    args = ap.parse_args()

    from repro.runtime import compile_cache, telemetry
    compile_cache.enable()

    from . import (bench_autotune, bench_bcsr, bench_fault, bench_levels,
                   bench_load_balance, bench_mesh2d, bench_mismatch,
                   bench_pallas_kernels, bench_replan, bench_replication,
                   bench_serving, bench_spadd3, bench_vs_interp,
                   bench_weak_scaling)
    from .common import drain_results

    print("name,us_per_call,derived")
    suites = {
        "vs_interp": lambda: bench_vs_interp.run(
            *((4000, 4000, 8) if args.quick else (20000, 20000, 16)),
            dims3=(400, 300, 200) if args.quick else (1200, 900, 500)),
        "spadd3": lambda: bench_spadd3.run(
            *( (2000, 2000) if args.quick else (8000, 8000) )),
        "load_balance": bench_load_balance.run,
        "mismatch": bench_mismatch.run,
        "weak_scaling": lambda: bench_weak_scaling.run(
            base_n=8000 if args.quick else 40000),
        "pallas_kernels": lambda: bench_pallas_kernels.run(
            n=4000 if args.quick else 20000),
        "bcsr": lambda: bench_bcsr.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64),
        "replan": lambda: bench_replan.run(
            *((2048, 2048) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64),
        "mesh2d": lambda: bench_mesh2d.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64),
        "levels": lambda: bench_levels.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64,
            dims3=(96, 64, 48) if args.quick else (256, 128, 96)),
        "autotune": lambda: bench_autotune.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=16 if args.quick else 64),
        "replication": lambda: bench_replication.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 128,
            dims3=(96, 64, 48) if args.quick else (256, 128, 96),
            L=8 if args.quick else 16),
        "fault": lambda: bench_fault.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64),
        "serving": lambda: bench_serving.run(
            *((1024, 1024) if args.quick else (4096, 4096)),
            j=32 if args.quick else 64),
    }
    only = {s for s in args.only.split(",") if s} if args.only else None
    if only:
        unknown = only - suites.keys()
        if unknown:
            ap.error(f"unknown suite(s): {', '.join(sorted(unknown))}; "
                     f"available: {', '.join(suites)}")
    for name, fn in suites.items():
        if only is not None and name not in only:
            continue
        drain_results()        # reset the registry for this suite
        telemetry.METRICS.clear()   # per-suite counters/gauges/histograms
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report, keep the harness going
            print(f"{name}_ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        if args.json:
            os.makedirs(args.out_dir, exist_ok=True)
            path = os.path.join(args.out_dir, f"BENCH_{name}.json")
            with open(path, "w") as fh:
                json.dump({"results": drain_results(),
                           "telemetry": telemetry.METRICS.snapshot()},
                          fh, indent=2, sort_keys=True)
            print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
