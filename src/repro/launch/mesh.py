"""Production mesh definitions (MULTI-POD DRY-RUN spec).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax
initialization.
"""
from __future__ import annotations

from ..distributed.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))
