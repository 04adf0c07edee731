"""``obs.count`` (a catalog counter increment that is also a trace
counter event) and the tracer's ``TraceAnnotation`` mirror."""

import os
import subprocess
import sys
import types

import pytest

from mythril_tpu import obs
from mythril_tpu.obs import catalog, metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def counter_events():
    return [e for e in obs.TRACER.raw_events() if e[0] == "C"]


def test_count_increments_without_tracing():
    obs.count(catalog.HOST_SAT_SOLVES_TOTAL, 1, "sat")
    assert catalog.HOST_SAT_SOLVES_TOTAL.value("sat") == 1
    assert counter_events() == []


def test_count_records_one_counter_event_while_tracing():
    obs.TRACER.enable()
    obs.count(catalog.SOLVER_OUTCOMES_TOTAL, 3, "triage", "unknown", pid=4)
    assert catalog.SOLVER_OUTCOMES_TOTAL.value("triage", "unknown") == 3
    (event,) = counter_events()
    kind, name, _track, pid, _ts, _dur, args = event
    assert (name, pid) == ("myth_solver_outcomes_total", 4)
    assert args["amount"] == 3
    assert args["labels"] == {"use": "triage", "outcome": "unknown"}
    (chrome,) = [e for e in obs.TRACER.chrome_events() if e["ph"] == "C"]
    assert chrome["name"] == 'myth_solver_outcomes_total{use="triage",outcome="unknown"}'
    assert chrome["args"] == {"value": 3}


def test_count_with_metrics_off_still_traces():
    metrics.set_enabled(False)
    obs.TRACER.enable()
    obs.count(catalog.DEVICE_LANE_SLOTS_TOTAL, 512)
    metrics.set_enabled(True)
    assert catalog.DEVICE_LANE_SLOTS_TOTAL.value() == 0
    assert [e[6]["amount"] for e in counter_events()] == [512]


@pytest.fixture
def fake_profiler(monkeypatch):
    """A stand-in ``jax.profiler`` whose annotations log enter/exit."""
    log = []

    class TraceAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    module = types.ModuleType("jax.profiler")
    module.TraceAnnotation = TraceAnnotation
    monkeypatch.setitem(sys.modules, "jax.profiler", module)
    return log


def _every_span_kind():
    with obs.phase("host_sat"):
        pass
    with obs.TRACER.span("decide_batch", tid="solve"):
        pass
    token = obs.TRACER.begin("fused_super_round", tid="device")
    obs.TRACER.end(token, rounds=3)


def test_annotation_mirror_only_while_tracing(fake_profiler):
    _every_span_kind()
    assert fake_profiler == []
    obs.TRACER.enable()
    _every_span_kind()
    assert fake_profiler == [
        ("enter", "host_sat"), ("exit", "host_sat"),
        ("enter", "decide_batch"), ("exit", "decide_batch"),
        ("enter", "fused_super_round"), ("exit", "fused_super_round"),
    ]
    (fused,) = [e for e in obs.TRACER.raw_events() if e[1] == "fused_super_round"]
    assert fused[6] == {"rounds": 3}


def test_tracing_imports_no_jax():
    code = (
        "import sys\n"
        "from mythril_tpu import obs\n"
        "from mythril_tpu.obs import catalog\n"
        "obs.TRACER.enable()\n"
        "with obs.phase('settle'):\n"
        "    obs.count(catalog.WARMUP_S, 1.0, 'download')\n"
        "with obs.TRACER.span('x'):\n"
        "    pass\n"
        "obs.TRACER.end(obs.TRACER.begin('y'))\n"
        "assert len(obs.TRACER.raw_events()) == 4\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
