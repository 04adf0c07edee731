"""Golden-schema trace test (ISSUE 9 satellite): a real tpu-batch
analysis of the stress-style contract with the tracer live produces a
valid Chrome trace-event document — required keys on every event, phase
spans strictly nested inside their round span — and, with a fault armed
at a seam, exactly one ``fault_injected`` instant event per planned
injection. Runs a REAL device pipeline on the CPU mesh; scripts/check.sh
deselects it by name ('golden') from the fast obs step."""

import json

import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu import obs
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.robustness import faults
from tests.service.test_multitenant import SUICIDE_SRC, contract_pair

REQUIRED_KEYS = {"ph", "ts", "dur", "pid", "tid", "name"}

# the round-loop phase taxonomy (docs/OBSERVABILITY.md); every one of
# these spans must nest inside a round span on the same process row
ROUND_PHASES = {
    "host_exec",
    "pack",
    "transfer_up",
    "device_round",
    "transfer_down",
    "lift",
    "triage",
    "solve",
    "harvest",
}


@pytest.fixture(autouse=True)
def always_engage(monkeypatch):
    monkeypatch.setattr(
        backend,
        "DEFAULT_BATCH_CFG",
        backend.DEFAULT_BATCH_CFG._replace(
            min_device_frontier=0, device_engage_after_s=0.0
        ),
    )


def run_traced_analysis(fault_spec=None):
    runtime, creation = contract_pair(SUICIDE_SRC)
    contract = EVMContract(code=runtime, creation_code=creation, name="T")
    obs.TRACER.enable()
    faults.configure(fault_spec)
    try:
        SymExecWrapper(
            contract,
            address=0x1234,
            strategy="tpu-batch",
            execution_timeout=240,
            transaction_count=1,
            max_depth=64,
        )
        return obs.TRACER.chrome_trace()
    finally:
        faults.configure(None)
        obs.TRACER.disable()


def test_becstress_trace_schema_and_round_nesting(tmp_path):
    doc = run_traced_analysis()
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(doc))
    doc = json.loads(path.read_text())

    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert events, "traced analysis recorded no events"
    for event in events:
        assert REQUIRED_KEYS <= set(event.keys()), event
        assert event["ph"] in ("X", "i", "C", "M"), event
        if event["ph"] != "M":
            assert event["ts"] >= 0 and event["dur"] >= 0, event
        if event["ph"] == "C":
            # a counter event carries one numeric increment
            assert isinstance(event["args"]["value"], (int, float)), event

    rounds = sorted(
        (e for e in events if e["ph"] == "X" and e["name"] == "round"),
        key=lambda e: e["ts"],
    )
    assert rounds, "no round spans recorded"
    # the cut mechanism yields a strictly sequential round track
    for prev, cur in zip(rounds, rounds[1:]):
        assert prev["ts"] + prev["dur"] <= cur["ts"] + 0.5, (prev, cur)

    phase_spans = [
        e for e in events if e["ph"] == "X" and e["name"] in ROUND_PHASES
    ]
    assert {e["name"] for e in phase_spans} >= {
        "host_exec", "pack", "transfer_up", "device_round",
        "transfer_down", "solve",
    }
    # strict nesting: every phase occurrence lies inside one round span
    # (0.5 us slack for microsecond rounding at export)
    intervals = [(r["ts"], r["ts"] + r["dur"]) for r in rounds]
    for span in phase_spans:
        lo, hi = span["ts"], span["ts"] + span["dur"]
        assert any(
            start - 0.5 <= lo and hi <= end + 0.5
            for start, end in intervals
        ), ("phase span outside every round", span)


def test_one_mark_per_injected_fault():
    doc = run_traced_analysis("transfer_up=error:n=2")
    marks = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "i" and e["name"] == "fault_injected"
    ]
    assert len(marks) == 2, marks
    assert all(m["args"]["seam"] == "transfer_up" for m in marks)
    # the absorbed faults also surface as retry incidents
    retries = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "i" and e["name"] == "device_retry"
    ]
    assert len(retries) >= 2
