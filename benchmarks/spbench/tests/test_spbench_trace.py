"""The trace reduction: busy union, idle share, collective sum, breakdown,
and the per-layer readers built on it."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(HERE), "..", "..", "src")]

import devtrace  # noqa: E402
import spec  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"
HOST = devtrace.HOST_PLANE
# Window 0..1000 ns. Device 0: ops at 100-300 and 200-400 (overlap) and
# 900-1100 (clipped to 1000); an all-reduce at 500-600. Device 1: 0-100.
SYNTH = {
    D0: {devtrace.OP_LINE: [["fusion.1", 100, 200], ["fusion.2", 200, 200],
                            ["all-reduce.3", 500, 100],
                            ["fusion.1", 900, 200]]},
    D1: {devtrace.OP_LINE: [["fusion.1", 0, 100]]},
    HOST: {"python": [["bench.window", 0, 1000], ["bench.rebind", 0, 90],
                      ["bench.run", 90, 700], ["bench.result", 790, 10],
                      ["bench.run", 800, 200]]},
}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    evs = devtrace.ops(SYNTH, D0)
    # [100, 400] + [500, 600] + [900, 1000]
    assert devtrace.busy_ns(evs, 0, 1000) == 300 + 100 + 100
    assert devtrace.busy_ns(evs, 150, 550) == 250 + 50


def test_collectives_are_summed_by_name():
    evs = devtrace.ops(SYNTH, D0)
    assert devtrace.collective_ns(evs, 0, 1000) == 100
    assert devtrace.collective_ns(devtrace.ops(SYNTH, D1), 0, 1000) == 0


def test_reduce_picks_the_busiest_device_and_names_idle_gaps():
    r = devtrace.reduce(SYNTH, [D0, D1])
    assert r["busiest"] == D0
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["per_device"][D0]["busy_s"] == pytest.approx(500e-9)
    assert r["per_device"][D1]["busy_s"] == pytest.approx(100e-9)
    assert r["per_device"][D0]["collective_s"] == pytest.approx(100e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-9)
    assert [g[0] for g in r["idle_gaps"]] == ["bench.run", "bench.rebind",
                                              "bench.run"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [300e-9, 100e-9, 100e-9])


def test_window_must_be_traced_once():
    bad = {D0: SYNTH[D0], HOST: {"python": [["bench.run", 0, 10]]}}
    with pytest.raises(ValueError):
        devtrace.window(bad)


def _ctx(trace, iterations=2, chips=1):
    return {"spans": [{"name": "lower", "dur_us": 2.5e6},
                      {"name": "lower.plan", "dur_us": 1e6}],
            "iterations": iterations, "rebind_s": [0.001, 0.003],
            "trace": trace, "work": {"bytes": 819e3, "flops": 0.0},
            "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "chips": chips}


def test_readers_on_the_synthetic_trace():
    red = devtrace.reduce(SYNTH, [D0, D1])
    read = {m: spec.module("layer_metrics", m).read for m in
            ("lower_s", "rebind_ms_per_iter", "device_ms_per_iter",
             "leaves_roofline", "device_idle_share",
             "collective_ms_per_iter")}
    ctx = _ctx(red)
    assert read["lower_s"](ctx) == pytest.approx(2.5)
    assert read["rebind_ms_per_iter"](ctx) == pytest.approx(2.0)
    assert read["device_ms_per_iter"](ctx) == pytest.approx(250e-6)
    assert read["device_idle_share"](ctx) == pytest.approx(0.5)
    assert read["collective_ms_per_iter"](ctx) == pytest.approx(50e-6)
    # 819e3 bytes at 819 GB/s = 1 us per iteration, against 250 ns busy:
    # the reduction reports what it reads, above 100% where the work or
    # the time is counted wrong.
    assert read["leaves_roofline"](ctx) == pytest.approx(400.0)


def test_readers_return_nothing_without_a_trace():
    ctx = _ctx(None)
    ctx["rebind_s"] = []
    for m in ("rebind_ms_per_iter", "device_ms_per_iter", "leaves_roofline",
              "device_idle_share", "collective_ms_per_iter"):
        assert spec.module("layer_metrics", m).read(ctx) is None


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three calls of a jitted gather +
    segment_sum (1M entries), each under ``bench.run``, in one
    ``bench.window`` of 62.622036 ms. The device ran three modules of
    16.65 ms each; the first began 1.457 ms before the window opened on
    the host clock, so 48.490 ms of busy time falls inside it."""
    import json
    with open(os.path.join(HERE, "data", "trace_small_1chip.json")) as fh:
        trace = json.load(fh)
    assert [p for p in trace if devtrace.DEVICE_PLANE.match(p)] == [D0]
    r = devtrace.reduce(trace, [D0])
    assert r["window_s"] == pytest.approx(0.062622036)
    busy = r["per_device"][D0]["busy_s"]
    assert busy == pytest.approx(0.048490173)
    assert r["per_device"][D0]["collective_s"] == 0
    gaps = r["idle_gaps"]
    # the window's idle time is exactly what the gaps add up to
    assert sum(g for _, g in gaps) == pytest.approx(r["window_s"] - busy)
    assert [g[0] for g in gaps[:3]] == ["bench.run"] * 3
    name, secs = r["device_ops"][0]
    assert name == "%fusion.1" and secs == pytest.approx(0.027494157)
    ctx = _ctx(r, iterations=3)
    idle = spec.module("layer_metrics", "device_idle_share").read(ctx)
    assert idle == pytest.approx(1 - busy / 0.062622036)
