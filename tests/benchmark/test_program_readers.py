"""The readers of the program's own spans and counts
(benchmark/lib/program_counts.py and the metrics that use it), on
synthetic tracer contents."""

import os
import types

import pytest

from benchmark import run as bench
from benchmark.lib import program_counts as pc
from benchmark.lib.result import Result

METRICS = os.path.join(bench.ROOT, "benchmark", "metrics")
NEW = ("host_sat_share.deep", "settle_share.deep", "solver_decided_share.deep",
       "fused_lane_use.deep", "warmup_share.deep")


def reader(name):
    return bench.load_module(os.path.join(METRICS, name + ".py"), "test_metric_" + name.replace(".", "_"))


def span(name, start, dur, track=None):
    return ("X", name, track or name, 0, start, dur, {})


def count(name, amount, **labels):
    return ("C", name, name, 0, 0.0, 0.0, {"amount": amount, "labels": labels})


class FakeTracer:
    def __init__(self, events, dropped=0):
        self._events = events
        self.dropped = dropped

    def raw_events(self):
        return list(self._events)


WINDOW = [
    span("host_exec", 0.0, 30.0),
    span("settle", 1.0, 10.0),
    span("host_sat", 2.0, 4.0),
    span("triage", 31.0, 5.0),
    span("host_sat", 32.0, 1.0),
    span("round", 0.0, 40.0, track="round"),
    count("myth_solver_batch_queries_total", 80, use="frontier"),
    count("myth_solver_batch_queries_total", 20, use="triage"),
    count("myth_solver_outcomes_total", 60, use="frontier", outcome="memo"),
    count("myth_solver_outcomes_total", 20, use="frontier", outcome="unknown"),
    count("myth_solver_outcomes_total", 20, use="triage", outcome="unknown"),
    count("myth_device_steps_total", 84924),
    count("myth_device_lane_slots_total", 1048576),
    count("myth_device_lane_slots_total", 1048576),
]
SNAPSHOT = {
    'myth_warmup_s{stage="fused_loop"}': 20.0,
    'myth_warmup_s{stage="solver_buckets"}': 10.0,
    'myth_round_phase_s_sum{phase="lift"}': 3.0,
    "myth_device_steps_total": 84924.0,
}
EXPECTED = {
    "host_sat_share.deep": 5.0 / 50.0,
    "settle_share.deep": 10.0 / 50.0,
    "solver_decided_share.deep": 60.0 / 100.0,
    "fused_lane_use.deep": 84924 / 2097152,
    "warmup_share.deep": 30.0 / 75.0,
}


def result():
    r = Result()
    r.window_s = 50.0
    r.setup_s = 75.0
    return r


@pytest.fixture
def program(monkeypatch):
    from mythril_tpu import obs

    def install(events, snapshot, dropped=0):
        monkeypatch.setattr(obs, "TRACER", FakeTracer(events, dropped))
        monkeypatch.setattr(obs, "REGISTRY", types.SimpleNamespace(snapshot=lambda: dict(snapshot)))

    return install


@pytest.mark.parametrize("name", NEW)
def test_reads_the_window(program, name):
    program(WINDOW, SNAPSHOT)
    assert reader(name).read(result()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_on_a_program_without_the_instruments(program, name):
    # the parent program: phase spans of its own, no new span or counter
    program([span("host_exec", 0.0, 30.0), span("lift", 30.0, 5.0)],
            {'myth_round_phase_s_sum{phase="lift"}': 5.0})
    assert reader(name).read(result()) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_when_the_tracer_dropped_events(program, name):
    program(WINDOW, SNAPSHOT, dropped=1)
    assert reader(name).read(result()) is None


@pytest.mark.parametrize("name", ("host_sat_share.deep", "settle_share.deep", "warmup_share.deep"))
def test_a_share_needs_its_base(program, name):
    # a window that never opened leaves no window or set-up wall
    program(WINDOW, SNAPSHOT)
    r = result()
    r.window_s = r.setup_s = None
    assert reader(name).read(r) is None


def test_counter_sums_filter_by_label():
    assert pc.counter_total(WINDOW, "myth_solver_outcomes_total", outcome="unknown") == 40
    assert pc.counter_by_label(WINDOW, "myth_solver_batch_queries_total", "use") == {
        "frontier": 80, "triage": 20}
    assert pc.registry_series(SNAPSHOT, "myth_warmup_s", "stage") == {
        "fused_loop": 20.0, "solver_buckets": 10.0}


def test_host_sat_split_by_the_innermost_enclosing_phase():
    split = pc.enclosing_split(WINDOW + [span("host_sat", 45.0, 1.0)], "host_sat")
    assert split == {"settle": 4.0, "triage": 1.0, "none": 1.0}
