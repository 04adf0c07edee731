"""Transaction-end settlement solves an outcome only while it is open.

The integer module solves a hazard's wrap and witness only until its
site is in the report; the mutation pruner solves value transfer only
for a state that mutated nothing. Both keep their decisions."""

from types import SimpleNamespace

import pytest

from mythril_tpu.analysis import solver
from mythril_tpu.analysis.module.modules import integer
from mythril_tpu.analysis.report import Report
from mythril_tpu.laser.evm.plugins.implementations.mutation_pruner import (
    MutationPruner,
)
from mythril_tpu.laser.evm.plugins.implementations.plugin_annotations import (
    MutationAnnotation,
)
from mythril_tpu.laser.evm.plugins.signals import PluginSkipWorldState
from mythril_tpu.laser.evm.time_handler import time_handler
from mythril_tpu.obs import catalog, metrics
from mythril_tpu.smt import symbol_factory


@pytest.fixture(autouse=True)
def skips(monkeypatch):
    """Settlement skips counted during the test, by reason."""
    # an earlier analysis in this process may have left a spent budget,
    # under which get_model refuses every solve
    monkeypatch.setattr(time_handler, "_deadline_ms", None)
    monkeypatch.setattr(metrics, "_ENABLED", True)
    base = {r: catalog.SETTLE_SKIPPED_TOTAL.value(r) for r in ("reported", "mutated")}
    return lambda reason: catalog.SETTLE_SKIPPED_TOTAL.value(reason) - base[reason]


class _Spy:
    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def __call__(self, *args, **kwargs):
        self.calls.append(args)
        return self.fn(*args, **kwargs)


# -- integer settlement ------------------------------------------------------


class _Origin:
    """What settlement reads of a hazard's origin state."""

    def __init__(self, pc):
        self.environment = SimpleNamespace(
            active_account=SimpleNamespace(contract_name="C"),
            active_function_name="f",
            code=SimpleNamespace(bytecode="00"),
        )
        self.world_state = SimpleNamespace(constraints=[])
        self.pc = pc

    def get_current_instruction(self):
        return {"address": self.pc}


class _EndState:
    """An end state whose path carried ``hazards`` into a sink."""

    def __init__(self, *hazards):
        self.sink = integer.HazardsReachedSink()
        self.sink.hazards = set(hazards)
        self.world_state = SimpleNamespace(constraints=[])
        self.mstate = SimpleNamespace(min_gas_used=0, max_gas_used=0)

    def get_annotations(self, kind):
        return [self.sink] if kind is integer.HazardsReachedSink else []


def _hazard(pc, name):
    return integer.OverflowHazard(
        _Origin(pc), "addition", symbol_factory.BitVecSym(name, 256) == 1
    )


@pytest.fixture
def module(monkeypatch):
    """A fresh integer module whose wrap and witness solves are spies
    that always find a wrap."""
    module = integer.IntegerArithmetics()
    wrap = _Spy(lambda hazard: True)
    witness = _Spy(lambda state, constraints: {"steps": []})
    monkeypatch.setattr(module, "_wrap_feasible", wrap)
    monkeypatch.setattr(solver, "get_transaction_sequence", witness)
    module.spies = SimpleNamespace(wrap=wrap, witness=witness)
    return module


def test_second_end_state_at_reported_site_is_not_solved(module, skips):
    # sibling paths: two origin states at one pc, each its own hazard
    first, second = _EndState(_hazard(7, "a")), _EndState(_hazard(7, "b"))
    module._settle(first)
    assert (len(module.spies.wrap.calls), len(module.spies.witness.calls)) == (1, 1)
    module._settle(second)
    assert (len(module.spies.wrap.calls), len(module.spies.witness.calls)) == (1, 1)
    assert len(module.issues) == 1
    assert skips("reported") == 1


def test_prescreen_asks_nothing_for_reported_site(module):
    module._settle(_EndState(_hazard(7, "a")))
    assert module.batch_prescreen_requests(_EndState(_hazard(7, "b")), set()) == []


def test_unreported_site_is_still_solved(module):
    reported, other = _hazard(7, "a"), _hazard(9, "c")
    module._settle(_EndState(reported))
    state = _EndState(_hazard(7, "b"), other)
    requests = module.batch_prescreen_requests(state, set())
    assert [token for token, _ in requests] == [other.origin_state]
    module._settle(state)
    assert module.spies.wrap.calls == [(reported,), (other,)]
    assert len(module.spies.witness.calls) == 2
    assert sorted(i.address for i in module.issues) == [7, 9]


def test_report_holds_one_issue_per_site(module, skips):
    for name in "abcd":
        module._settle(_EndState(_hazard(7, name), _hazard(9, name + "x")))
    report = Report()
    for issue in module.issues:
        report.append_issue(issue)
    assert len(module.issues) == len(report.issues) == 2
    assert skips("reported") == 6


# -- mutation pruner ---------------------------------------------------------


def _pruner_hook():
    hooks = {}
    vm = SimpleNamespace(
        pre_hook=lambda op: (lambda fn: fn),
        laser_hook=lambda name: (lambda fn: hooks.setdefault(name, fn)),
    )
    MutationPruner().initialize(vm)
    return hooks["add_world_state"]


def _call_state(mutated, constraints):
    annotations = [MutationAnnotation()] if mutated else []
    callvalue = symbol_factory.BitVecSym("pruner_callvalue", 256)
    return SimpleNamespace(
        current_transaction=SimpleNamespace(),
        environment=SimpleNamespace(callvalue=callvalue),
        world_state=SimpleNamespace(constraints=list(constraints(callvalue))),
        get_annotations=lambda kind: iter(
            annotations if kind is MutationAnnotation else []
        ),
    )


@pytest.mark.parametrize(
    "mutated, constraints, kept, solves",
    [
        (True, lambda cv: [cv == 0], True, 0),
        (False, lambda cv: [], True, 1),
        (False, lambda cv: [cv == 0], False, 1),
    ],
    ids=["mutated", "callvalue-free", "callvalue-zero"],
)
def test_pruner_solves_only_clean_states(
    monkeypatch, skips, mutated, constraints, kept, solves
):
    spy = _Spy(solver.get_model)
    monkeypatch.setattr(solver, "get_model", spy)
    hook = _pruner_hook()
    try:
        hook(_call_state(mutated, constraints))
        outcome = True
    except PluginSkipWorldState:
        outcome = False
    assert outcome is kept
    assert len(spy.calls) == solves
    assert skips("mutated") == int(mutated)

