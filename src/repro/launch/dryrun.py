import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell: build the step function,
``.lower()`` with ShapeDtypeStruct inputs, ``.compile()``, and record
memory_analysis / cost_analysis / the HLO-derived roofline terms
(launch/roofline.py). The 512 placeholder host devices exist ONLY here —
the XLA_FLAGS line above precedes every other import by design.

Usage:
    python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --multi-pod both
Results land in experiments/dryrun/<arch>_<shape>_<mesh>.json.
"""
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from pathlib import Path  # noqa: E402

import jax          # noqa: E402

from ..configs.base import all_archs, get_arch  # noqa: E402
from .mesh import make_production_mesh          # noqa: E402
from .roofline import V5E, roofline_report         # noqa: E402
from . import steps as steps_mod                # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def model_flops_per_device(cfg, shape, mesh) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) per device; decode counts one
    token per sequence, forward-only shapes count 2·N·D."""
    n_chips = mesh.devices.size
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / n_chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / n_chips
    tokens = shape.global_batch
    return 2.0 * n_active * tokens / n_chips


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             save_hlo: bool = False) -> dict:
    cfg = get_arch(arch_name)
    shape = cfg.shapes()[shape_name]
    mesh_tag = "pod512" if multi_pod else "pod256"
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
           "status": "error"}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            jf, args, lm = steps_mod.build_step(cfg, shape, mesh)
            lowered = jf.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            rep = roofline_report(
                hlo, device_kind=V5E,   # the production mesh is v5e pods
                model_flops_per_device=model_flops_per_device(
                    cfg, shape, mesh))
            rec.update({
                "status": "ok",
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "n_devices": int(mesh.devices.size),
                "grad_accum": getattr(jf, "accum", shape.grad_accum),
                "memory": {
                    "argument_bytes_per_dev": mem.argument_size_in_bytes,
                    "output_bytes_per_dev": mem.output_size_in_bytes,
                    "temp_bytes_per_dev": mem.temp_size_in_bytes,
                    "alias_bytes_per_dev": mem.alias_size_in_bytes,
                    "peak_estimate_gib": round(
                        (mem.argument_size_in_bytes +
                         mem.output_size_in_bytes +
                         mem.temp_size_in_bytes -
                         mem.alias_size_in_bytes) / 2**30, 3),
                },
                "cost_analysis_flops_bodyonce": ca.get("flops", 0.0),
                "roofline": rep,
            })
            if save_hlo:
                (OUT_DIR / f"{arch_name}_{shape_name}_{mesh_tag}.hlo.txt"
                 ).write_text(hlo)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 2)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out = OUT_DIR / f"{arch_name}_{shape_name}_{mesh_tag}.json"
    out.write_text(json.dumps(rec, indent=2, default=float))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="off")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = list(all_archs()) if args.arch == "all" else [args.arch]
    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]
    for a in archs:
        cfg = get_arch(a)
        shapes = list(cfg.shapes()) if args.shape == "all" else [args.shape]
        for s in shapes:
            for mp in pods:
                rec = run_cell(a, s, mp, save_hlo=args.save_hlo)
                tag = "ok" if rec["status"] == "ok" else "FAIL"
                extra = ("" if rec["status"] == "ok"
                         else " :: " + rec.get("error", "?"))
                mem = rec.get("memory", {}).get("peak_estimate_gib", "-")
                print(f"[{tag}] {a} {s} {rec['mesh']} wall={rec['wall_s']}s "
                      f"mem/dev={mem}GiB{extra}", flush=True)


if __name__ == "__main__":
    main()
