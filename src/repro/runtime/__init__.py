"""Runtime subsystems: checkpointing, elastic execution, fault harness,
telemetry, the persistent compilation cache.

Submodules load lazily (PEP 562): ``core.lower`` and the other pipeline
modules import :mod:`repro.runtime.telemetry` at module scope, and an
eager ``from . import elastic`` here would pull ``distributed`` (and
through it ``core``) back in while ``core.lower`` is still initializing.
"""
import importlib

__all__ = ["checkpoint", "compile_cache", "elastic", "fault", "telemetry"]


def __getattr__(name):
    if name in __all__:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + __all__)
