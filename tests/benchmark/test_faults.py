"""The rest of a run, with the timed path broken underneath.

Each cell's driver runs as in a benchmark run, past the look for a chip,
with the program's analyses replaced by outputs the program really
produced (data/recorded_outputs.json). Unbroken, the run is correct;
with each fault the cell can have planted where the answers are
produced, ``correct`` comes out false: an answer altered (a moved
location, a wrong witness, a witness whose wrapping transaction then
reverts), half of the answers left out, and a device step that hands its
state back unchanged (no instruction retired on the device). One chip
has no exchange between chips to leave out.
"""

import copy
import json
import os
import time
import types

import pytest

from benchmark import control
from benchmark import run as bench
from benchmark.lib.check import Analysis
from benchmark.lib.redeploy import Deployment

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FAULTS = ["none", "pc", "witness", "drop-half", "unchanged-state", "revert-after-wrap"]


def _revert_after_wrap(analyses):
    """Every BECToken witness of the MUL at pc 28 sends value 2**255 + 1
    instead of 2**255: cnt * value still wraps, now to 2 * cnt, which the
    sender's empty balance cannot cover, so the transaction reverts after
    the wrap. The ADD witnesses stay as the program produced them."""
    for a in analyses:
        for issue in a.issues:
            if issue["address"] == 28:
                step = issue["tx_sequence"]["steps"][-1]
                data = step["input"]  # 0x, selector, then 32-byte words
                value = int(data[74:138], 16) + 1
                step["input"] = data[:74] + "%064x" % value + data[138:]


def _outputs(contracts, fault):
    with open(os.path.join(DATA, "recorded_outputs.json")) as fh:
        outputs = [o for o in json.load(fh) if o["contract"] in contracts]
    analyses = [Analysis(Deployment(o["contract"], o["name"], o["creation"], o["runtime"]),
                         o["tx_count"], copy.deepcopy(o["issues"]), device_steps=o["device_steps"])
                for o in outputs]
    if fault in control.TAMPERS:
        control.TAMPERS[fault](types.SimpleNamespace(analyses=analyses))
    if fault == "unchanged-state":
        for a in analyses:
            a.device_steps = 0
    if fault == "revert-after-wrap":
        _revert_after_wrap(analyses)
    return analyses


class _Laser:
    def __init__(self):
        self.total_states = 0
        self.strategy = types.SimpleNamespace(device_steps_retired=0, device_rounds=1,
                                              fused_rounds=1, fused_syncs=1)
        self.hooks = []

    def register_laser_hooks(self, kind, hook):
        self.hooks.append(hook)


class _Issue:
    def __init__(self, as_dict):
        self.as_dict = as_dict


class _Program:
    """The program's entry points, answering every deployment with what
    the program once produced for it."""

    def __init__(self, analyses):
        self.by_creation = {a.deployment.creation: a for a in analyses}
        self.deployments = [a.deployment for a in analyses]

    def redeploy(self, contract, runtime, rng, serial):
        mine = [d for d in self.deployments if d.contract == contract]
        return mine[serial % len(mine)]

    def sym_exec_wrapper(self, contract, address, strategy, transaction_count, pre_exec_hook=None, **kw):
        time.sleep(0.01)  # an analysis takes time
        a = self.by_creation[contract.creation_code]
        laser = _Laser()
        if pre_exec_hook is not None:
            pre_exec_hook(laser)
        for _ in range(transaction_count):
            for hook in laser.hooks:
                hook()
            laser.total_states += 10
            laser.strategy.device_steps_retired += a.device_steps
        return types.SimpleNamespace(laser=laser, issues=a.issues)

    def fire_lasers(self, sym, modules=None):
        return [_Issue(copy.deepcopy(i)) for i in sym.issues]


def _run_cell(workload, seconds, monkeypatch, analyses):
    program = _Program(analyses)
    monkeypatch.setattr("mythril_tpu.laser.tpu.backend.warmup_device", lambda cfg: None)
    monkeypatch.setattr("mythril_tpu.laser.tpu.backend.find_tpu_strategy", lambda strategy: strategy)
    monkeypatch.setattr("mythril_tpu.analysis.symbolic.SymExecWrapper", program.sym_exec_wrapper)
    monkeypatch.setattr("mythril_tpu.analysis.security.fire_lasers", program.fire_lasers)
    monkeypatch.setattr("benchmark.lib.redeploy.redeploy", program.redeploy)
    spec = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    cell, config, traffic = bench.resolve(spec, workload)
    compiles = types.SimpleNamespace(count=0, names=[])
    run = bench.Run(cell, config, traffic, 2 ** 32 + 5, seconds, False, compiles)
    return bench.execute(run, spec, {"platform": "cpu", "kind": "cpu", "count": 1})


CELLS = {
    # cell: (contracts whose outputs stand in, window seconds, faults it can have)
    "bectoken-t3": (["bectoken"], 0.2, FAULTS),
}


@pytest.mark.parametrize("workload,fault", [(w, f) for w, (_, _, faults) in CELLS.items() for f in faults])
def test_a_planted_fault_makes_the_run_incorrect(workload, fault, monkeypatch):
    contracts, seconds, _ = CELLS[workload]
    line = _run_cell(workload, seconds, monkeypatch, _outputs(contracts, fault))
    assert line["attempted"] >= 1
    assert line["correct"] is (fault == "none"), line["checks"]
