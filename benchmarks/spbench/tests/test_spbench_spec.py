"""Lookup by name in BENCHMARK.json, refusal of unknown names and device
kinds, and the shape of the benchmark's own files."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(HERE), "..", "..", "src")]

import peaks  # noqa: E402
import spec  # noqa: E402

BENCH = spec.load()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = spec.cell(BENCH, name)
    assert cell["workload"]["name"] == name
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(cell["readers"][m["name"]].read)


def test_unknown_workload_config_mix_and_metric_are_refused():
    with pytest.raises(spec.UnknownName):
        spec.cell(BENCH, "no_such_cell")
    with pytest.raises(spec.UnknownName):
        spec.config(BENCH, "no_such_config")
    with pytest.raises(spec.UnknownName):
        spec.traffic("no_such_mix")
    with pytest.raises(spec.UnknownName):
        spec.module("layer_metrics", "no_such_metric")
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["traffic"] = "no_such_mix"
    with pytest.raises(spec.UnknownName):
        spec.cell(bad, bad["workloads"][0]["name"])


@pytest.mark.parametrize("where,key", [("configs", "reference"),
                                       ("configs", "generator"),
                                       ("traffic", "loop")])
def test_unknown_statement_generator_and_loop_are_refused(where, key,
                                                          monkeypatch):
    name = CELLS[0]
    if where == "configs":
        real = spec.config
        monkeypatch.setattr(spec, "config", lambda b, n: dict(
            real(b, n), **{key: "no_such_module"}))
    else:
        real = spec.traffic
        monkeypatch.setattr(spec, "traffic", lambda n: dict(
            real(n), **{key: "no_such_module"}))
    with pytest.raises(spec.UnknownName):
        spec.cell(BENCH, name)


def test_unknown_device_kind_is_refused():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_per_layer_metrics_list_only_cells_reporting_what_they_move():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in CELLS
    for name in CELLS:
        cell = spec.cell(BENCH, name)
        moved = {m["name"] for m in cell["end_to_end"]}
        assert all(m["moves"] in moved for m in cell["per_layer"])


def test_files_live_under_paths_and_configs_name_their_reduced_keys():
    paths = BENCH["paths"]
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        cfg = spec.config(BENCH, c["name"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["source"] == c["source"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
