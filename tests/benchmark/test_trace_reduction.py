"""The profiler-trace reduction (benchmark/lib/trace.py)."""

import glob
import os

import pytest

from benchmark.lib.trace import TRACED, Event, load_events, program_spans, reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TPU0, TPU1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def _ev(plane, line, name, start_ms, end_ms):
    return Event(plane, line, name, start_ms * 1e6, end_ms * 1e6)


def _synthetic():
    return [
        _ev(HOST, "python", TRACED, 0, 100),
        _ev(HOST, "python", "bench.sym_exec", 0, 100),
        _ev(HOST, "python", "bench.fire_lasers", 70, 100),
        # device 0: overlapping ops 10..30, one op 50..60, one op sticking out of the window
        _ev(TPU0, "XLA Ops", "fusion.1", 10, 25),
        _ev(TPU0, "XLA Ops", "while.2", 20, 30),
        _ev(TPU0, "XLA Ops", "fusion.1", 50, 60),
        _ev(TPU0, "XLA Ops", "copy.3", 95, 120),
        _ev(TPU0, "XLA Modules", "jit__fused_impl(7)", 10, 30),
        _ev(TPU0, "XLA Modules", "jit_other(8)", 50, 60),
        # device 1: busy 40 ms
        _ev(TPU1, "XLA Ops", "fusion.1", 0, 40),
        _ev(TPU1, "XLA Modules", "jit__fused_impl(7)", 0, 40),
    ]


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    r = reduce(_synthetic())
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.1)
    # device 0: [10,30] + [50,60] + [95,100] = 35 ms; device 1: 40 ms
    assert r["busy_s"] == pytest.approx((0.035 + 0.040) / 2)
    assert r["program_s"] == pytest.approx((0.020 + 0.040) / 2)


def test_top_ops_and_idle_gaps_are_labelled():
    r = reduce(_synthetic())
    assert r["device_ops"][0][0] == "fusion.1"
    assert r["device_ops"][0][1] == pytest.approx((0.025 + 0.040) / 2)
    gaps = r["idle_gaps"]
    # device 0 idles 0..10, 30..50, 60..95; the last one is inside fire_lasers
    assert [g[1] for g in gaps] == pytest.approx([0.035, 0.020, 0.010])
    assert gaps[0][0] == "bench.fire_lasers"
    assert gaps[1][0] == "bench.sym_exec"


def test_gaps_take_the_program_phase_that_covers_most_of_them():
    phases = [Event("program", "triage", "triage", 62e6, 90e6), Event("program", "lift", "lift", 31e6, 49e6)]
    gaps = reduce(_synthetic(), host_spans=phases)["idle_gaps"]
    assert [g[0] for g in gaps] == ["triage", "lift", "bench.sym_exec"]


def test_program_spans_are_put_on_the_trace_clock():
    raw = [("X", "lift", "lift", 0, 10.5, 0.25, {}), ("X", "round", "round", 0, 10.0, 5.0, {}),
           ("i", "retry", "incident", 0, 11.0, 0.0, {})]
    spans = program_spans(raw, t_traced=10.0, traced_start_ns=2e9)
    assert spans == [Event("program", "lift", "lift", 2.5e9, 2.75e9)]


def test_a_trace_without_devices_reads_no_busy_time():
    r = reduce([_ev(HOST, "python", TRACED, 0, 10)])
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["idle_gaps"] == []


def test_recorded_chip_trace():
    """A short trace recorded on one TPU v5e: a jitted program run in a
    loop under the harness's own annotations."""
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    events = load_events(paths[0])
    r = reduce(events, program="jit_step")
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    # the program's executions hold its ops: the two readings agree
    assert r["program_s"] == pytest.approx(r["busy_s"], rel=0.05)
    assert r["device_ops"][0][0] == "%fusion"
    assert r["idle_gaps"][0][0] == "bench.fire_lasers"
