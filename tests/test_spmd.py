"""Multi-device SPMD coherence tests.

These run in a SUBPROCESS with ``--xla_force_host_platform_device_count``
(the main pytest process must keep 1 device), proving the shard_map
executor and the pjit'd LM step shard correctly — the small-scale version
of the multi-pod dry-run.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code))
    # Pin the platform: without JAX_PLATFORMS the stripped subprocess env
    # makes jax probe for TPUs (libtpu is installed in this image) and
    # spend minutes timing out against the GCE metadata server.
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=timeout,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              "JAX_PLATFORMS": os.environ.get(
                                  "JAX_PLATFORMS", "cpu")})
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_shard_map_spmv_executor():
    out = run_sub("""
        import numpy as np
        import repro.core as rc
        from repro.core.lower import (default_nnz_schedule,
                                      default_row_schedule, lower)
        from repro.core.tensor import Tensor
        from repro.data.spdata import powerlaw_matrix
        from repro.distributed.executor import to_spmd
        from repro.distributed.mesh import machine_to_mesh

        M = rc.Machine(("x", 8))
        B = powerlaw_matrix("B", 500, 400, 8, seed=0)
        c = Tensor.from_dense("c", np.random.default_rng(1)
                              .standard_normal(400).astype(np.float32))
        a = Tensor.zeros_dense("a", (500,))
        stmt = rc.parse_tin("a(i) = B(i,j) * c(j)", a=a, B=B, c=c)
        exp = B.to_dense() @ np.asarray(c.to_dense())
        mesh = machine_to_mesh(M)
        for sched in (default_row_schedule(stmt, M),
                      default_nnz_schedule(stmt, M)):
            k = lower(stmt, M, schedule=sched)
            y = to_spmd(k, mesh)()
            assert np.allclose(y, exp, atol=1e-3), k.leaf_name
            # simulation backend and SPMD backend agree exactly
            assert np.allclose(y, k.run(), atol=1e-5)
        print("SPMD_OK")
    """)
    assert "SPMD_OK" in out


def test_pjit_train_step_on_mesh():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_arch, ShapeConfig
        from repro.distributed import planner
        from repro.distributed.mesh import make_mesh
        from repro.launch import steps as steps_mod
        from repro.optim.adamw import adamw_init

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_arch("llama3-8b").reduced()
        shape = ShapeConfig("t", "train", seq_len=32, global_batch=8,
                            grad_accum=2)
        with mesh:
            lm = steps_mod.build_lm(cfg, mesh)
            fn, accum = steps_mod.make_train_step(lm, shape, mesh)
            params = lm.init_params(jax.random.PRNGKey(0))
            p_sh = planner.shardings_from(
                planner.params_pspecs(params, mesh), mesh)
            params = jax.device_put(params, p_sh)
            opt = adamw_init(params)
            tokens = jnp.zeros((8, 32), jnp.int32)
            new_p, new_opt, m = jax.jit(fn)(params, opt, tokens)
            assert np.isfinite(float(m["loss"]))
            # params stay sharded after the step
            leaf = jax.tree.leaves(new_p)[0]
            assert len(leaf.sharding.device_set) > 1
        print("PJIT_OK", float(m["loss"]))
    """)
    assert "PJIT_OK" in out


def test_decode_step_on_mesh_with_cache_sharding():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_arch
        from repro.distributed import planner
        from repro.distributed.mesh import make_mesh
        from repro.launch import steps as steps_mod

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_arch("qwen3-14b").reduced()
        with mesh:
            lm = steps_mod.build_lm(cfg, mesh)
            params = lm.init_params(jax.random.PRNGKey(0))
            cache = lm.init_cache(8, 64)
            c_sh = planner.shardings_from(
                planner.cache_pspecs(cache, mesh, 8), mesh)
            cache = jax.device_put(cache, c_sh)
            tok = jnp.zeros((8,), jnp.int32)
            logits, cache2 = jax.jit(
                lambda p, c, t: lm.decode_step(p, c, t))(params, cache, tok)
            assert not np.any(np.isnan(np.asarray(logits, np.float32)))
            assert int(np.asarray(cache2["pos"])[0]) == 1
        print("DECODE_OK")
    """)
    assert "DECODE_OK" in out


def test_hierarchical_grad_reduce_three_axes():
    out = run_sub("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import hierarchical_grad_reduce
        from repro.distributed.mesh import make_mesh

        mesh = make_mesh((2, 4), ("pod", "data"))

        # identical local gradient on every device: the hierarchical
        # reduce-scatter(data) -> all-reduce(pod) -> all-gather(data)
        # must equal a flat psum over all 8 devices, i.e. g * 8.
        # check_vma=False: the reduce-scatter/all-gather pair restores
        # replication over 'data' but the static varying-axes check cannot
        # infer that through psum_scatter.
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)
        def reduce_fn(g):
            return hierarchical_grad_reduce({"g": g}, intra_axis="data",
                                            inter_axis="pod")["g"]

        g = jnp.arange(8.0 * 4).reshape(8, 4)
        got = reduce_fn(g)
        assert np.allclose(np.asarray(got), np.asarray(g) * 8), got
        print("HIER_OK")
    """)
    assert "HIER_OK" in out


def test_shard_map_spmm_and_sddmm_executors():
    out = run_sub("""
        import numpy as np
        import repro.core as rc
        from repro.core.lower import (default_nnz_schedule,
                                      default_row_schedule, lower)
        from repro.core.tensor import Tensor
        from repro.data.spdata import powerlaw_matrix
        from repro.distributed.executor import to_spmd
        from repro.distributed.mesh import machine_to_mesh

        M = rc.Machine(("x", 8))
        mesh = machine_to_mesh(M)
        rng = np.random.default_rng(0)
        B = powerlaw_matrix("B", 400, 300, 8, seed=0)
        dB = B.to_dense()

        # SpMM rows
        dC = rng.standard_normal((300, 16)).astype(np.float32)
        C = Tensor.from_dense("C", dC)
        A = Tensor.zeros_dense("A", (400, 16))
        smm = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)", A=A, B=B, C=C)
        k1 = lower(smm, M)
        assert np.allclose(to_spmd(k1, mesh)(), dB @ dC, atol=1e-3)

        # SDDMM nnz
        K = 8
        dCc = rng.standard_normal((400, K)).astype(np.float32)
        dDd = rng.standard_normal((K, 300)).astype(np.float32)
        Ap = Tensor("A", B.shape, B.format, B.levels,
                    np.ones_like(B.vals), B.dtype)
        sd = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)", A=Ap, B=B,
                          C=Tensor.from_dense("C", dCc),
                          D=Tensor.from_dense("D", dDd))
        k2 = lower(sd, M, schedule=default_nnz_schedule(sd, M))
        flat = to_spmd(k2, mesh)()
        pos, crd = B.levels[1].pos, B.levels[1].crd
        rows = np.repeat(np.arange(400), np.diff(pos))
        exp = B.vals * (dCc[rows] * dDd[:, crd].T).sum(1)
        assert np.allclose(flat, exp, atol=1e-3)
        print("SPMD2_OK")
    """)
    assert "SPMD2_OK" in out


def test_shard_map_format_general_executors():
    """Format-general lowering survives the real shard_map backend: a DCSR
    operand under the nnz SpMM executor and a COO operand under the
    row-based SDDMM executor (densified-root view) both match the oracle."""
    out = run_sub("""
        import numpy as np
        import repro.core as rc
        from repro.core import formats as F
        from repro.core.lower import (default_nnz_schedule,
                                      default_row_schedule, lower)
        from repro.core.tensor import Tensor
        from repro.distributed.executor import to_spmd
        from repro.distributed.mesh import machine_to_mesh

        M = rc.Machine(("x", 8))
        rng = np.random.default_rng(2)
        n, m, K = 96, 80, 8
        dB = ((rng.random((n, m)) < 0.1) *
              rng.standard_normal((n, m))).astype(np.float32)
        dB[5] = 0
        mesh = machine_to_mesh(M)

        B = Tensor.from_dense("B", dB, F.DCSR())
        dC = rng.standard_normal((m, 6)).astype(np.float32)
        stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                            A=Tensor.zeros_dense("A", (n, 6)), B=B,
                            C=Tensor.from_dense("C", dC))
        k = lower(stmt, M, schedule=default_nnz_schedule(stmt, M))
        assert k.fallbacks == [], k.fallbacks
        y = to_spmd(k, mesh)()
        assert np.allclose(y, dB @ dC, atol=1e-3), k.leaf_name

        Bc = Tensor.from_dense("B", dB, F.COO(2))
        dCc = rng.standard_normal((n, K)).astype(np.float32)
        dDd = rng.standard_normal((K, m)).astype(np.float32)
        A = Tensor.from_dense("A", (dB != 0) * 1.0, F.CSR())
        stmt2 = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)", A=A, B=Bc,
                             C=Tensor.from_dense("C", dCc),
                             D=Tensor.from_dense("D", dDd))
        k2 = lower(stmt2, M, schedule=default_row_schedule(stmt2, M))
        assert k2.fallbacks == [], k2.fallbacks
        flat = to_spmd(k2, mesh)()
        got = Tensor("A", Bc.shape, Bc.format, Bc.levels, flat, Bc.dtype)
        exp = (dB != 0) * dB * (dCc @ dDd)
        assert np.allclose(got.to_dense(), exp, atol=1e-3), k2.leaf_name
        print("FG_SPMD_OK")
    """)
    assert "FG_SPMD_OK" in out


def test_shard_map_grid_2d_executors():
    """Multi-axis distribution on a GENUINE Mesh((4, 2), ("x", "y")):
    grid rows cells psum along y only (SUMMA), grid nnz cells shard the
    flat color axis over both mesh axes and psum over both. All agree
    with the vmap simulation backend and the dense oracle."""
    out = run_sub("""
        import numpy as np
        import repro.core as rc
        from repro.core import formats as F
        from repro.core.lower import (default_grid_nnz_schedule,
                                      default_grid_schedule, lower)
        from repro.core.tensor import Tensor
        from repro.distributed.executor import to_spmd
        from repro.distributed.mesh import machine_to_mesh

        rng = np.random.default_rng(0)
        n, m, J, K = 60, 44, 9, 5
        dB = ((rng.random((n, m)) < 0.25) *
              rng.standard_normal((n, m))).astype(np.float32)
        M = rc.Machine(("x", 4), ("y", 2))
        mesh = machine_to_mesh(M)
        assert mesh.devices.shape == (4, 2)

        # SpMM, rows grid (csr + bcsr): reduction scoped to y
        for fm in (F.CSR(), F.BCSR((2, 2))):
            B = Tensor.from_dense("B", dB, fm)
            C = Tensor.from_dense(
                "C", rng.standard_normal((m, J)).astype(np.float32))
            stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                                A=Tensor.zeros_dense("A", (n, J)), B=B, C=C)
            k = lower(stmt, M, schedule=default_grid_schedule(stmt, M))
            y = to_spmd(k, mesh)()
            assert np.allclose(y, dB @ C.to_dense(), atol=1e-3), k.leaf_name
            assert np.allclose(y, k.run(), atol=1e-5), k.leaf_name

        # SpMV + SDDMM rows grid, SpMV nnz grid (flat colors over x AND y)
        B = Tensor.from_dense("B", dB, F.CSR())
        c = Tensor.from_dense(
            "c", rng.standard_normal(m).astype(np.float32))
        stmt = rc.parse_tin("a(i) = B(i,j) * c(j)",
                            a=Tensor.zeros_dense("a", (n,)), B=B, c=c)
        for sched in (default_grid_schedule(stmt, M),
                      default_grid_nnz_schedule(stmt, M)):
            k = lower(stmt, M, schedule=sched)
            y = to_spmd(k, mesh)()
            assert np.allclose(y, dB @ np.asarray(c.to_dense()),
                               atol=1e-3), k.leaf_name
            assert np.allclose(y, k.run(), atol=1e-5), k.leaf_name

        Cs = Tensor.from_dense(
            "C", rng.standard_normal((n, K)).astype(np.float32))
        D = Tensor.from_dense(
            "D", rng.standard_normal((K, m)).astype(np.float32))
        A = Tensor.from_dense("A", (dB != 0) * 1.0, F.CSR())
        stmt = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)",
                            A=A, B=B, C=Cs, D=D)
        k = lower(stmt, M, schedule=default_grid_schedule(stmt, M))
        y = to_spmd(k, mesh)()
        assert np.allclose(y, np.asarray(k.run().vals), atol=1e-4)
        print("GRID_SPMD_OK")
    """)
    assert "GRID_SPMD_OK" in out


def test_shard_map_grid_3d_and_replicated_executors():
    """3-D mesh executors (ISSUE 7): 2.5-D replicated SpMM/SDDMM with
    psum scoped to exactly the reduction axis the replication leaves,
    brick SpMTTKRP with psum over (y, z), and the device-count guard."""
    out = run_sub("""
        import numpy as np
        import pytest
        import repro.core as rc
        from repro.core import formats as F
        from repro.core.lower import (default_grid3_schedule,
                                      default_replicated_schedule, lower)
        from repro.core.tensor import Tensor
        from repro.distributed.executor import to_spmd
        from repro.distributed.mesh import machine_to_mesh, make_mesh

        rng = np.random.default_rng(0)
        M = rc.Machine(("x", 2), ("y", 2), ("z", 2))
        mesh = machine_to_mesh(M)
        n, m, J, K = 37, 29, 10, 5

        # 2.5-D replicated SpMM: psum over y only
        dB = ((rng.random((n, m)) < .25) *
              rng.standard_normal((n, m))).astype(np.float32)
        B = Tensor.from_dense("B", dB, F.CSR())
        C = Tensor.from_dense(
            "C", rng.standard_normal((m, J)).astype(np.float32))
        stmt = rc.parse_tin("A(i,j) = B(i,k) * C(k,j)",
                            A=Tensor.zeros_dense("A", (n, J)), B=B, C=C)
        k = lower(stmt, M, schedule=default_replicated_schedule(stmt, M))
        y = to_spmd(k, mesh)()
        assert np.allclose(y, dB @ np.asarray(C.to_dense()), atol=1e-3)
        assert np.allclose(y, k.run(), atol=1e-5)

        # 2.5-D replicated SDDMM: psum over z only
        Cs = Tensor.from_dense(
            "C", rng.standard_normal((n, K)).astype(np.float32))
        D = Tensor.from_dense(
            "D", rng.standard_normal((K, m)).astype(np.float32))
        A = Tensor.from_dense("A", (dB != 0) * 1.0, F.CSR())
        stmt = rc.parse_tin("A(i,j) = B(i,j) * C(i,k) * D(k,j)",
                            A=A, B=B, C=Cs, D=D)
        k = lower(stmt, M, schedule=default_replicated_schedule(stmt, M))
        y = to_spmd(k, mesh)()
        assert np.allclose(y, np.asarray(k.run().vals), atol=1e-4)

        # brick SpMTTKRP: psum over (y, z)
        n3, m3, p3, L = 17, 13, 11, 6
        dB3 = ((rng.random((n3, m3, p3)) < .1) *
               rng.standard_normal((n3, m3, p3))).astype(np.float32)
        stmt = rc.parse_tin(
            "A(i,l) = B(i,j,k) * C(j,l) * D(k,l)",
            A=Tensor.zeros_dense("A", (n3, L)),
            B=Tensor.from_dense("B", dB3, F.COO(3)),
            C=Tensor.from_dense(
                "C", rng.standard_normal((m3, L)).astype(np.float32)),
            D=Tensor.from_dense(
                "D", rng.standard_normal((p3, L)).astype(np.float32)))
        k = lower(stmt, M, schedule=default_grid3_schedule(stmt, M))
        y = to_spmd(k, mesh)()
        assert np.allclose(y, k.run(), atol=1e-4)

        # oversized grid fails FAST with the device count in the message
        try:
            make_mesh((4, 4, 4), ("x", "y", "z"))
            raise SystemExit("mesh guard did not fire")
        except ValueError as e:
            assert "64 pieces" in str(e) and "8 visible" in str(e), str(e)
        print("GRID3_SPMD_OK")
    """)
    assert "GRID3_SPMD_OK" in out
