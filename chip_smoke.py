#!/usr/bin/env python3
"""Smoke run of the sparse compiler's main path on a TPU v5e.

Drives the entry points a user calls — ``lower_stmt`` and ``kernel.run()``
under hand schedules and ``schedule="auto"``, the blocked (BCSR) tile
leaves, ``SparseKernelServer`` — at deployment sizes on one chip, and with
``--chips 4`` only the ``to_spmd`` mesh path and what it is compared with.
Every phase is checked against ``scipy.sparse`` in float64, built from the
operand's own coordinates and values, never through the lowering.

    python3 chip_smoke.py [--seed 0]        # one chip
    python3 chip_smoke.py --chips 4         # 4-chip mesh path

Each phase prints one ``phase {...}`` JSON line: cell id, leaf, fallbacks,
nnz, bytes shipped to the device, set-up (lower) seconds, first-run
(compile) seconds, steady ``run()`` seconds (each call returns host
arrays, so it ends after the device finished), and the error beside its
tolerance. These are set-up and smoke timings, not benchmark results. The
last line is the ``{"ok": true, "device": {...}}`` record, printed only
when every phase passed. Without a TPU the script exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Error bound of every phase: max |got - ref| / max |ref|, with ref the
# float64 scipy result. float32 operands and float32 accumulation over rows
# of up to a few million terms stay orders of magnitude below it; a float32
# matmul run as a single bf16 pass (~4e-3) does not.
TOL = 1e-4

# Deployment sizes. Each phase's program was compiled for a described v5e
# first; a size whose memory_analysis() left less than a quarter of the
# chip's 16 GB free was halved, and CUTS says so on every run.
SPMV_N = 1 << 22          # powerlaw CSR, 16 nnz/row on average
SPMM_N, SPMM_J = 1 << 19, 128
SDDMM_N, SDDMM_K = 1 << 18, 64
BCSR_N, BCSR_BW, BCSR_J = 1 << 20, 8, 128
SERVE_WAVES, SERVE_BATCH = 4, 8
STEADY_ITERS = 3
CUTS = ["sddmm n 2^19 -> 2^18: the nnz program's memory_analysis() on v5e "
        "was 12.59 GB at 2^19 (limit 12 GB), 5.32 GB at 2^18"]


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _scipy_csr(B):
    """float64 scipy CSR from a CSR Tensor's coordinates and values."""
    import scipy.sparse as sp
    coords = B.coords()
    return sp.csr_matrix(
        (B.vals.astype(np.float64), (coords[:, 0], coords[:, 1])),
        shape=B.shape)


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    _check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    _check(bool(np.isfinite(got).all()), "non-finite output")
    scale = max(float(np.abs(ref).max()), np.finfo(np.float64).tiny)
    return float(np.abs(got - ref).max() / scale)


def _shard_bytes(kernel) -> int:
    return int(sum(np.asarray(a).nbytes for sh in kernel.shards.values()
                   for a in sh.arrays.values()))


def _dense(name, rng, shape):
    from repro.core.tensor import Tensor
    return Tensor.from_dense(
        name, rng.standard_normal(shape).astype(np.float32))


def _report(cell, kernel, nnz, err, *, setup_s, first_s, steady_s, **extra):
    _check(kernel.fallbacks == [], f"{cell}: fallbacks {kernel.fallbacks}")
    _check(not kernel.leaf_name.startswith("generic"),
           f"{cell}: generic host fallback {kernel.leaf_name}")
    _check(err <= TOL, f"{cell}: error {err!r} > tolerance {TOL!r}")
    rec = {"phase": cell, "cell": kernel.cell_id(), "leaf": kernel.leaf_name,
           "fallbacks": kernel.fallbacks, "nnz": int(nnz),
           "device_bytes": _shard_bytes(kernel), "setup_s": setup_s,
           "first_run_s": first_s, "steady_run_s": steady_s,
           "err": err, "tol": TOL, **extra}
    print("phase " + json.dumps(rec), flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _run_kernel(kernel):
    """First run (compile + transfer + execute), then the steady median."""
    out, first = _timed(kernel.run)
    steady = []
    for _ in range(STEADY_ITERS):
        out, dt = _timed(kernel.run)
        steady.append(dt)
    return out, first, float(np.median(steady))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def spmv_stmt(B, c):
    import repro.core as rc
    from repro.core.tensor import Tensor
    return rc.parse_tin("a(i) = B(i,j) * c(j)",
                        a=Tensor.zeros_dense("a", (B.shape[0],)), B=B, c=c)


def phase_spmv(B, c, Bsp, machine):
    """CSR SpMV under the row and the non-zero schedule."""
    import repro.core as rc
    stmt = spmv_stmt(B, c)
    ref = Bsp @ c.vals.astype(np.float64)
    for name, sched in (("spmv_csr_rows", rc.default_row_schedule),
                        ("spmv_csr_nnz", rc.default_nnz_schedule)):
        k, setup = _timed(lambda: rc.lower_stmt(
            stmt, machine, schedule=sched(stmt, machine)))
        y, first, steady = _run_kernel(k)
        _report(name, k, B.nnz, _rel_err(y, ref), setup_s=setup,
                first_s=first, steady_s=steady)


def phase_spmm_auto(B, C, Bsp, machine):
    """CSR SpMM through the autoscheduler: the planner times its top-K
    candidates on the device, and any candidate that failed fails here."""
    import repro.core as rc
    from repro.core.tensor import Tensor
    stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (B.shape[0], C.shape[1])),
                        B=B, C=C)
    k, setup = _timed(lambda: rc.lower_stmt(stmt, machine, schedule="auto"))
    cands = k.tuned.candidates or []
    failed = [c for c in cands if c.get("error")]
    _check(not failed, "auto: failed candidates:\n" + k.explain())
    y, first, steady = _run_kernel(k)
    ref = Bsp @ C.vals.astype(np.float64)
    _report("spmm_csr_auto", k, B.nnz, _rel_err(y, ref), setup_s=setup,
            first_s=first, steady_s=steady, winner=k.tuned.label,
            candidates=[{key: c[key] for key in ("label", "measured_s")}
                        for c in cands])


def phase_sddmm(B, C, D, machine):
    """CSR SDDMM under the non-zero schedule; reference sampled per
    stored position in float64."""
    import repro.core as rc
    from repro.core.tensor import Tensor
    A = Tensor("A", B.shape, B.format, B.levels,
               np.ones_like(B.vals), B.dtype)
    stmt = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)",
                        A=A, B=B, C=C, D=D)
    k, setup = _timed(lambda: rc.lower_stmt(
        stmt, machine, schedule=rc.default_nnz_schedule(stmt, machine)))
    out, first, steady = _run_kernel(k)
    coords = B.coords()
    C64, D64 = C.vals.astype(np.float64), D.vals.astype(np.float64)
    ref = np.empty(B.nnz, np.float64)
    for s in range(0, B.nnz, 1 << 20):          # bounded host memory
        r, q = coords[s:s + (1 << 20), 0], coords[s:s + (1 << 20), 1]
        ref[s:s + r.shape[0]] = B.vals[s:s + r.shape[0]] * np.einsum(
            "nk,kn->n", C64[r], D64[:, q])
    _report("sddmm_csr_nnz", k, B.nnz, _rel_err(out.vals, ref),
            setup_s=setup, first_s=first, steady_s=steady)


def banded_bcsr(n, bandwidth, block, seed):
    """The banded matrix as CSR (the reference's source) and as BCSR
    assembled straight from its coordinates (no dense image)."""
    from repro.core import formats as F
    from repro.core.tensor import Tensor
    from repro.data.spdata import banded_matrix
    Bc = banded_matrix("B", n, bandwidth, seed=seed)
    coords = Bc.coords().astype(np.int64)
    br, bc = block
    gc = -(-n // bc)
    key = (coords[:, 0] // br) * gc + coords[:, 1] // bc
    uniq, inv = np.unique(key, return_inverse=True)
    tiles = np.zeros((uniq.shape[0], br, bc), np.float32)
    tiles[inv, coords[:, 0] % br, coords[:, 1] % bc] = Bc.vals
    Bb = Tensor.from_blocks("B", (n, n), F.BCSR(block),
                            np.stack([uniq // gc, uniq % gc], 1), tiles,
                            dedupe=False)
    return Bc, Bb


def phase_bcsr_spmm(Bc, Bb, C, machine):
    """Blocked SpMM: every stored (8, 8) tile is an MXU matmul."""
    import repro.core as rc
    from repro.core.tensor import Tensor
    stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (Bb.shape[0], C.shape[1])),
                        B=Bb, C=C)
    k, setup = _timed(lambda: rc.lower_stmt(
        stmt, machine, schedule=rc.default_row_schedule(stmt, machine)))
    y, first, steady = _run_kernel(k)
    ref = _scipy_csr(Bc) @ C.vals.astype(np.float64)
    _report("spmm_bcsr8x8_rows", k, Bc.nnz, _rel_err(y, ref),
            setup_s=setup, first_s=first, steady_s=steady,
            stored_blocks=int(Bb.vals.shape[0]))


def phase_serve(B, Bsp, machine, rng):
    """SparseKernelServer over the SpMV operand: waves of request vectors,
    each wave one batched SpMM."""
    from repro.core.cache import batch_bucket
    from repro.core.tensor import Tensor
    from repro.launch.serve import SparseKernelServer
    stmt = spmv_stmt(B, Tensor.zeros_dense("c", (B.shape[1],)))
    srv, setup = _timed(lambda: SparseKernelServer(
        stmt, machine, max_batch=SERVE_BATCH))
    err, waves = 0.0, []
    for _ in range(SERVE_WAVES):
        xs = rng.standard_normal((SERVE_BATCH, B.shape[1])).astype(np.float32)
        rids = [srv.submit(x) for x in xs]
        _, dt = _timed(srv.step)
        waves.append(dt)
        ref = Bsp @ xs.T.astype(np.float64)
        got = np.stack([srv.result(r) for r in rids], 1)
        err = max(err, _rel_err(got, ref))
    entry = srv.kernel._entries[batch_bucket(SERVE_BATCH, srv.kernel.buckets)]
    _report("serve_spmv_csr", entry.kernel, B.nnz, err, setup_s=setup,
            first_s=waves[0], steady_s=float(np.median(waves[1:])),
            requests=SERVE_WAVES * SERVE_BATCH, **srv.stats())


def run_one_chip(seed: int) -> None:
    import repro.core as rc
    from repro.data.spdata import powerlaw_matrix
    rng = np.random.default_rng(seed)
    machine = rc.Machine(("x", 4))

    B = powerlaw_matrix("B", SPMV_N, SPMV_N, 16, seed=seed)
    Bsp = _scipy_csr(B)
    phase_spmv(B, _dense("c", rng, (SPMV_N,)), Bsp, machine)
    phase_serve(B, Bsp, machine, rng)
    del B, Bsp

    B = powerlaw_matrix("B", SPMM_N, SPMM_N, 16, seed=seed + 1)
    phase_spmm_auto(B, _dense("C", rng, (SPMM_N, SPMM_J)), _scipy_csr(B),
                    machine)
    B = powerlaw_matrix("B", SDDMM_N, SDDMM_N, 16, seed=seed + 2)
    phase_sddmm(B, _dense("C", rng, (SDDMM_N, SDDMM_K)),
                _dense("D", rng, (SDDMM_K, SDDMM_N)), machine)
    Bc, Bb = banded_bcsr(BCSR_N, BCSR_BW, (8, 8), seed + 3)
    phase_bcsr_spmm(Bc, Bb, _dense("C", rng, (BCSR_N, BCSR_J)), machine)


# ---------------------------------------------------------------------------
# Four-chip phase: to_spmd against the one-device run and the reference
# ---------------------------------------------------------------------------

def _sparse_bytes_per_device(call) -> dict:
    """Bytes of the sparse operand on each device, read from the arrays
    ``to_spmd`` placed. The builders this script drives take the sparse
    operand's three arrays (pos/crd/vals or rows/cols/vals) first."""
    per = {}
    for arr in call.placed[:3]:
        for s in arr.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def _spmd_case(cell, stmt, machine, sched, ref, nnz):
    import repro.core as rc
    from repro.distributed.executor import to_spmd
    k, setup = _timed(lambda: rc.lower_stmt(stmt, machine, schedule=sched))
    one, _, one_steady = _run_kernel(k)
    call, place_s = _timed(lambda: to_spmd(k))
    y, first = _timed(call)
    steady = []
    for _ in range(STEADY_ITERS):
        y, dt = _timed(call)
        steady.append(dt)
    per = _sparse_bytes_per_device(call)
    total = sum(per.values())
    _check(len(per) == 4, f"{cell}: sparse operand on {sorted(per)}")
    shares = {d: b / total for d, b in per.items()}
    _check(all(0.2 <= s <= 0.3 for s in shares.values()),
           f"{cell}: per-device shares {shares}")
    err_one = _rel_err(y, np.asarray(one, np.float64))
    _check(err_one <= TOL, f"{cell}: spmd vs one-device {err_one!r}")
    _report(cell, k, nnz, _rel_err(y, ref), setup_s=setup + place_s,
            first_s=first, steady_s=float(np.median(steady)),
            one_device_steady_s=one_steady, err_vs_one_device=err_one,
            sparse_bytes_per_device={str(d): b for d, b in
                                     sorted(per.items())})


def run_four_chips(seed: int) -> None:
    import repro.core as rc
    from repro.core.tensor import Tensor
    from repro.data.spdata import powerlaw_matrix
    rng = np.random.default_rng(seed)
    m1 = rc.Machine(("x", 4))
    B = powerlaw_matrix("B", SPMV_N, SPMV_N, 16, seed=seed)
    c = _dense("c", rng, (SPMV_N,))
    stmt = spmv_stmt(B, c)
    ref = _scipy_csr(B) @ c.vals.astype(np.float64)
    _spmd_case("spmd_spmv_csr_rows_4x1", stmt, m1,
               rc.default_row_schedule(stmt, m1), ref, B.nnz)
    _spmd_case("spmd_spmv_csr_nnz_4x1", stmt, m1,
               rc.default_nnz_schedule(stmt, m1), ref, B.nnz)
    del B, stmt, ref

    m2 = rc.Machine(("x", 2), ("y", 2))
    B = powerlaw_matrix("B", SPMM_N, SPMM_N, 16, seed=seed + 1)
    C = _dense("C", rng, (SPMM_N, SPMM_J))
    stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                        A=Tensor.zeros_dense("A", (SPMM_N, SPMM_J)),
                        B=B, C=C)
    _spmd_case("spmd_spmm_csr_grid_2x2", stmt, m2,
               rc.default_grid_schedule(stmt, m2),
               _scipy_csr(B) @ C.vals.astype(np.float64), B.nnz)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the to_spmd mesh path on four chips")
    args = ap.parse_args()

    import jax
    from repro.launch.roofline import V5E
    from repro.runtime import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on another backend", file=sys.stderr)
        return 2
    _check(dev.device_kind == V5E,
           f"device kind {dev.device_kind!r} is not {V5E!r}")
    _check(len(devices) >= args.chips,
           f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)

    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        for cut in CUTS:
            print(f"cut: {cut}", flush=True)
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
