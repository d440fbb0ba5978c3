"""Find a cell's pieces by name: the entries of ``BENCHMARK.json`` and the
files they name under this directory. An unknown name is refused."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class UnknownName(LookupError):
    pass


def load() -> dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"unknown {what} {name!r}; known: "
                      f"{sorted(e['name'] for e in entries)}")


def _json(path: Path, what: str, name: str) -> dict:
    if not path.is_file():
        raise UnknownName(f"no {what} file for {name!r} at {path}")
    with open(path) as fh:
        return json.load(fh)


def config(bench: dict, name: str) -> dict:
    entry = _by_name(bench["configs"], name, "config")
    return _json(ROOT / entry["file"], "config", name)


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json", "traffic mix", name)


_MODULES = {}


def module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, imported from its file
    once per process."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise UnknownName(f"no {kind} module {name!r} at {path}")
    if path not in _MODULES:
        mod_spec = importlib.util.spec_from_file_location(
            f"spbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def cell(bench: dict, name: str) -> dict:
    """Everything one run of cell ``name`` needs, looked up by name: the
    configuration and its generator (``data/``) and statement module
    (``reference/``), the traffic mix and its loop (``loops/``), and the
    reader of each per-layer metric (``layer_metrics/``)."""
    wl = _by_name(bench["workloads"], name, "workload")
    cfg = config(bench, wl["config"])
    mix = traffic(wl["traffic"])
    if int(mix["chips"]) != int(wl["chips"]):
        raise ValueError(f"{name}: traffic {wl['traffic']!r} asks for "
                         f"{mix['chips']} chips, the cell for {wl['chips']}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e_names
                              else [])]
    readers = {m["name"]: module("layer_metrics", m["name"]) for m in layer}
    return {"workload": wl, "config": cfg, "traffic": mix,
            "generator": module("data", cfg["generator"]),
            "reference": module("reference", cfg["reference"]),
            "loop": module("loops", mix["loop"]),
            "end_to_end": e2e, "per_layer": layer, "readers": readers}
