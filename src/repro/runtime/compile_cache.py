"""JAX's persistent compilation cache for this checkout's entry points.

Compiling the leaf programs at deployment sizes takes seconds to minutes,
and a later process with the same programs can read them back instead.
:func:`enable` is called by the entry points that drive the chip
(``chip_smoke.py``, ``benchmarks/run.py``) — never at import, so tests and
library users keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache/`` (listed in ``.gitignore``), one fixed place
that every later run from the same checkout finds again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory, used when the environment names none.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
