#!/usr/bin/env python3
"""Run one cell of the sparse compiler's chip benchmark.

    python3 benchmarks/spbench/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``. With ``--trace 0``
the last stdout line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The run refuses, with no result line, a backend other than a TPU,
a ``device_kind`` the peak table lacks, and fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import peaks as peaks_mod  # noqa: E402
import spec  # noqa: E402


class Refused(RuntimeError):
    pass


def check_device(chips: int) -> dict:
    """Peaks of the chip this run holds; refuses anything but enough TPUs
    of a kind the peak table knows."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devs)}")
    return peaks_mod.peaks_for(devs[0].device_kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(spec.load(), args.workload)
    try:
        peaks = check_device(int(cell["workload"]["chips"]))
    except (Refused, peaks_mod.UnknownDevice) as e:
        print(f"spbench: refused: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.runtime import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {cache_dir}", flush=True)

    import harness
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
