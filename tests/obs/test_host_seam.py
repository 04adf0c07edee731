"""Spans and counts at the host seam: host SAT solves, decide_batch
verdicts, the fused loop's lane use, and an adaptive K that telemetry
cannot move."""

import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu import obs
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.laser.tpu import solver_cache as sc
from mythril_tpu.obs import catalog, metrics
from mythril_tpu.smt import ULT, symbol_factory
from mythril_tpu.smt.solver.incremental import IncrementalCore
from tests.service.test_multitenant import SUICIDE_SRC, contract_pair


def bv(name):
    return symbol_factory.BitVecSym(name, 16)


def val(v):
    return symbol_factory.BitVecVal(v, 16)


def spans(name):
    return [e for e in obs.TRACER.raw_events() if e[0] == "X" and e[1] == name]


def counts(name):
    return [e[6] for e in obs.TRACER.raw_events() if e[0] == "C" and e[1] == name]


@pytest.mark.parametrize("values, result", [((5,), "sat"), ((5, 6), "unsat")])
def test_each_host_solve_is_one_span_and_one_count(values, result):
    core = IncrementalCore()
    a = bv("hs_%s" % result)
    lowered = [core.lower((a == val(v)).raw) for v in values]
    obs.TRACER.enable()
    core.solve_checked([lit for lit, _ in lowered], [rw for _, rw in lowered],
                       timeout_ms=10_000)
    assert len(spans("host_sat")) == 1
    assert counts("myth_host_sat_solves_total") == [
        {"amount": 1, "labels": {"result": result}}
    ]
    assert catalog.HOST_SAT_SOLVES_TOTAL.value(result) == 1


def test_decide_batch_outcomes_sum_to_queries():
    cache = sc.SolverCache()
    a = bv("db_a")
    sat_set = [(a == val(3)).raw]
    unsat_set = [(a == val(1)).raw, (a == val(2)).raw]
    seeded = [ULT(a, val(9)).raw]
    obs.TRACER.enable()
    cache.decide_batch([sat_set, unsat_set, seeded], use_device=False,
                       static_unsat=[False, False, True])
    # the memo answers the repeat; triage never reaches the host
    cache.decide_batch([sat_set, [ULT(a, val(99)).raw]], use_device=False,
                       host_fallback=False, use="triage")
    for use, queries in (("frontier", 3), ("triage", 2)):
        outcomes = {
            o: catalog.SOLVER_OUTCOMES_TOTAL.value(use, o)
            for o in ("memo", "rewrite", "static", "witness", "device", "host", "unknown")
        }
        assert catalog.SOLVER_BATCH_QUERIES_TOTAL.value(use) == queries
        assert sum(outcomes.values()) == queries
        traced = sum(c["amount"] for c in counts("myth_solver_outcomes_total")
                     if c["labels"]["use"] == use)
        assert traced == queries
    assert catalog.SOLVER_OUTCOMES_TOTAL.value("frontier", "static") == 1
    assert catalog.SOLVER_OUTCOMES_TOTAL.value("triage", "memo") == 1
    assert catalog.SOLVER_OUTCOMES_TOTAL.value("triage", "unknown") == 1
    assert len(spans("decide_batch")) == 2


@pytest.fixture
def always_engage(monkeypatch):
    monkeypatch.setattr(
        backend,
        "DEFAULT_BATCH_CFG",
        backend.DEFAULT_BATCH_CFG._replace(
            min_device_frontier=0, device_engage_after_s=0.0
        ),
    )


def test_fused_path_counts_lane_slots_and_no_synthetic_rounds(always_engage):
    runtime, creation = contract_pair(SUICIDE_SRC)
    contract = EVMContract(code=runtime, creation_code=creation, name="T")
    obs.TRACER.enable()
    sym = SymExecWrapper(
        contract, address=0x1234, strategy="tpu-batch", execution_timeout=240,
        transaction_count=1, max_depth=64,
    )
    strategy = backend.find_tpu_strategy(sym.laser.strategy)
    assert strategy.fused_rounds > 0
    assert catalog.ROUND_PHASE_S.count("device_round_iter") == 0
    names = {e[1] for e in obs.TRACER.raw_events()}
    assert "device_round_iter" not in names
    steps = catalog.DEVICE_STEPS_TOTAL.value()
    slots = catalog.DEVICE_LANE_SLOTS_TOTAL.value()
    assert 0 < steps <= slots
    assert sum(c["amount"] for c in counts("myth_device_lane_slots_total")) == slots
    fused = [e[6] for e in spans("fused_super_round")]
    assert fused and all({"rounds", "n_alive", "n_running"} <= set(a) for a in fused)
    assert sum(a["rounds"] for a in fused) >= strategy.fused_rounds
    # settlement ran at the committed transaction end, inside host_exec
    assert spans("settle")


def test_adaptive_k_ignores_the_telemetry_switch(monkeypatch):
    monkeypatch.delenv("MYTHRIL_TPU_FUSED_K", raising=False)
    monkeypatch.setattr(backend, "_fused_round_cost_s", [0.01])
    clock = [0.0]
    monkeypatch.setattr(backend.time, "perf_counter", lambda: clock[0])

    def k_after(walls):
        monkeypatch.setattr(backend, "_HOST_WALLS", backend._PhaseWalls())
        for phase, wall in walls:
            with backend._host_phase(phase, 0):
                clock[0] += wall
        return backend._pick_fused_k()

    walls = [("host_exec", 0.2), ("lift", 0.1), ("solve", 0.05)] * 3
    k_on = k_after(walls)
    metrics.set_enabled(False)
    k_off = k_after(walls)
    assert k_on == k_off == 35
    assert k_on != backend.FUSED_K_DEFAULT
