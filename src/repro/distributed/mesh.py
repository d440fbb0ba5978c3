"""Mesh utilities — the bridge between the paper's abstract Machine grids
and `jax.sharding.Mesh`.

`machine_to_mesh` realizes a TDN Machine as a JAX mesh (axis names map
one-to-one), so the same Machine object drives both the sparse-kernel
partition plans and the SPMD executor. All mesh constructors are FUNCTIONS
— importing this module never touches jax device state (the dry-run must
set XLA_FLAGS first).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from ..core.tdn import Machine


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    want = int(np.prod(np.asarray(shape, dtype=np.int64))) if len(shape) else 1
    have = len(jax.devices())
    if want > have:
        raise ValueError(
            f"machine grid {tuple(int(s) for s in shape)} "
            f"({'×'.join(str(int(s)) for s in shape)} = {want} pieces) "
            f"exceeds the {have} visible device(s); shrink the grid or "
            f"expose more devices (e.g. XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want} on CPU)")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def machine_to_mesh(machine: Machine) -> Mesh:
    return make_mesh([d.size for d in machine.dims],
                     [d.name for d in machine.dims])


def mesh_to_machine(mesh: Mesh) -> Machine:
    return Machine(*[(n, s) for n, s in
                     zip(mesh.axis_names, mesh.devices.shape)])


def resize_machine(machine: Machine, axis: str, size: int) -> Machine:
    """A new Machine with ``axis`` resized to ``size`` — the mesh-as-data
    primitive: machines are values, so elastic resize is construction, not
    mutation of trace state."""
    names = [d.name for d in machine.dims]
    if axis not in names:
        raise ValueError(f"machine has no axis {axis!r} (axes: {names})")
    if size < 1:
        raise ValueError(f"axis size must be >= 1, got {size}")
    return Machine(*[(d.name, size if d.name == axis else d.size)
                     for d in machine.dims])


def shrink_machine(machine: Machine, axis: Optional[str] = None,
                   by: int = 1) -> Machine:
    """The P→P−1 device-loss resize: shrink ``axis`` (default: the first
    dimension) by ``by`` pieces."""
    axis = axis if axis is not None else machine.dims[0].name
    cur = {d.name: d.size for d in machine.dims}.get(axis)
    if cur is None:
        raise ValueError(f"machine has no axis {axis!r}")
    if cur - by < 1:
        raise ValueError(
            f"cannot shrink axis {axis!r} from {cur} by {by}: no pieces left")
    return resize_machine(machine, axis, cur - by)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes used for data parallelism ('pod' composes with 'data')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh: Mesh, *axes: str) -> int:
    s = 1
    for a in axes:
        if a in mesh.axis_names:
            s *= mesh.devices.shape[mesh.axis_names.index(a)]
    return s
