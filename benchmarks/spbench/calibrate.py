#!/usr/bin/env python3
"""Control readings of a configuration's correctness check.

    python3 benchmarks/spbench/calibrate.py --config <name> --seeds 1,2,3

For each seed it builds the configuration's operand and the dense
operands of a run, and prints the number that decides ``correct`` for the
control: the float64 reference computed from bfloat16-rounded operands,
put in the program's place. A limit holds only where every control reading
lies above it (``reference/<name>.py``). The control runs on the host and needs
no chip; the benchmark's own runs never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--pool", type=int, default=4)
    args = ap.parse_args(argv)
    cfg = spec.config(spec.load(), args.config)
    gen = spec.module("data", cfg["generator"])
    stmt_mod = spec.module("reference", cfg["reference"])
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        B = gen.generate(cfg, seed, cfg["sparse"])
        pool = stmt_mod.operands(cfg, B, args.pool, seed)
        ref = stmt_mod.Reference(B)
        errs = [ref.control_error(d) for d in pool]
        readings.append(min(errs))
        print(json.dumps({"config": args.config, "seed": seed,
                          "nnz": int(B.nnz), "control_rel_err": errs,
                          "limit": float(cfg["err_limit"]),
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"config": args.config,
                      "control_min": min(readings),
                      "limit": float(cfg["err_limit"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
