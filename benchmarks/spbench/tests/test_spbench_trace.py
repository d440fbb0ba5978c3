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
# 900-1100 (clipped to 1000); an all-reduce at 500-600; two modules. Its
# idle gaps 0-100, 400-500 and 600-900 fall under these host spans:
#   0-10 none, 10-90 bench.rebind, 90-100 bench.run,
#   400-450 run.copy_in, 450-500 run.execute,
#   600-620 run.execute, 620-700 run (copy-out), 700-790 bench.run,
#   790-800 bench.result, 800-850 bench.run, 850-880 run.copy_in,
#   880-900 run.execute.
# Device 1: 0-100.
SYNTH = {
    D0: {devtrace.OP_LINE: [["fusion.1", 100, 200], ["fusion.2", 200, 200],
                            ["all-reduce.3", 500, 100],
                            ["fusion.1", 900, 200]],
         devtrace.MODULE_LINE: [["jit_spmv_nnz(11)", 100, 300],
                                ["jit_spmv_nnz(11)", 500, 100],
                                ["jit_other", 900, 200]]},
    D1: {devtrace.OP_LINE: [["fusion.1", 0, 100]]},
    HOST: {"python": [["bench.window", 0, 1000], ["bench.rebind", 10, 80],
                      ["bench.run", 90, 700], ["run", 350, 350],
                      ["run.copy_in", 350, 100], ["run.execute", 450, 170],
                      ["bench.result", 790, 10], ["bench.run", 800, 200],
                      ["run", 850, 150], ["run.copy_in", 850, 30],
                      ["run.execute", 880, 120]]},
}
IDLE_NS = {"none": 10, "bench.rebind": 80, "bench.run": 10 + 90 + 50,
           "bench.result": 10, "run.copy_in": 50 + 30,
           "run.execute": 50 + 20 + 20, "run": 80}


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
                                              "run.execute"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [300e-9, 100e-9, 100e-9])


def test_module_seconds_inside_the_window_by_module_name():
    r = devtrace.reduce(SYNTH, [D0, D1])
    assert r["modules"] == pytest.approx({"jit_spmv_nnz": 400e-9,
                                          "jit_other": 100e-9})
    assert sum(r["modules"].values()) == pytest.approx(
        r["per_device"][D0]["busy_s"])


def test_idle_is_cut_at_span_boundaries_and_charged_to_the_innermost():
    r = devtrace.reduce(SYNTH, [D0, D1])
    assert r["idle_by_span"] == pytest.approx(
        {k: v * 1e-9 for k, v in IDLE_NS.items()})
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["per_device"][D0]["busy_s"])


def test_idle_by_span_names_every_open_span_and_skips_instants():
    spans = [["bench.window", 0, 100], ["bench.run", 0, 100],
             ["run", 20, 0], ["run.copy_in", 40, 20]]
    got = devtrace.idle_by_span([["op", 40, 20]], spans, 0, 100)
    assert got == pytest.approx({"bench.run": 80e-9, "run.copy_in": 0.0})
    assert devtrace.idle_by_span([], [], 0, 100) == pytest.approx(
        {"none": 100e-9})


def test_window_must_be_traced_once():
    bad = {D0: SYNTH[D0], HOST: {"python": [["bench.run", 0, 10]]}}
    with pytest.raises(ValueError):
        devtrace.window(bad)


#: Readers of the program's counters and of its spans in the trace.
PROGRAM = ("compile_s", "h2d_mb_per_iter", "d2h_mb_per_iter",
           "copy_in_idle_ms_per_iter", "copy_out_idle_ms_per_iter")


def _ctx(trace, iterations=2, chips=1):
    return {"spans": [{"name": "lower", "dur_us": 2.5e6},
                      {"name": "lower.plan", "dur_us": 1e6}],
            "setup_counters": {"jit.compile_s": 3.5, "jit.compiles": 2},
            "counters": {"run.h2d_bytes": 3e6, "run.d2h_bytes": 1e6,
                         "run.calls": 2},
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


def test_program_readers_on_the_synthetic_trace():
    red = devtrace.reduce(SYNTH, [D0, D1])
    read = {m: spec.module("layer_metrics", m).read for m in PROGRAM}
    ctx = _ctx(red)
    assert read["compile_s"](ctx) == pytest.approx(3.5)
    assert read["h2d_mb_per_iter"](ctx) == pytest.approx(1.5)
    assert read["d2h_mb_per_iter"](ctx) == pytest.approx(0.5)
    # 80 ns of idle time under each, over 2 iterations, in ms
    assert read["copy_in_idle_ms_per_iter"](ctx) == pytest.approx(40e-6)
    assert read["copy_out_idle_ms_per_iter"](ctx) == pytest.approx(40e-6)


def test_readers_return_nothing_without_a_trace():
    ctx = _ctx(None)
    ctx.update(rebind_s=[], setup_counters={}, counters={})
    for m in ("rebind_ms_per_iter", "device_ms_per_iter", "leaves_roofline",
              "device_idle_share", "collective_ms_per_iter") + PROGRAM:
        assert spec.module("layer_metrics", m).read(ctx) is None


def test_copy_idle_readers_return_nothing_without_program_spans():
    """A trace whose window holds no kernel call of the program."""
    bench_only = dict(SYNTH, **{HOST: {"python": [
        sp for sp in SYNTH[HOST]["python"] if sp[0].startswith("bench.")]}})
    ctx = _ctx(devtrace.reduce(bench_only, [D0, D1]))
    for m in ("copy_in_idle_ms_per_iter", "copy_out_idle_ms_per_iter"):
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
