"""Settling each finding once keeps the findings and their witnesses.

The host engine runs the benchmark's contracts at depths where every
documented hazard is reachable. The reported ``(swc, address, title)``
set is pinned to what the engine reported when settlement still solved
every end state, and each witness kept must show its hazard when
replayed on the plain reference EVM (``benchmark/lib/evm.py``)."""

import pytest

from benchmark.lib import evm
from benchmark.lib.redeploy import creation_for, load_contract
from mythril_tpu.analysis.analysis_args import analysis_args
from mythril_tpu.analysis.module.util import reset_callback_modules
from mythril_tpu.analysis.security import fire_lasers
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.ethereum.evmcontract import EVMContract

# the target address `myth analyze -c <creation code>` analyses at
CLI_ADDRESS = "0x" + "0" * 38 + "06"

FINDINGS = [
    ("bectoken", 1, {("101", 28, "Integer Overflow"), ("101", 104, "Integer Overflow")}),
    ("killable", 2, {("106", 19, "Unprotected Selfdestruct")}),
    ("originauth", 2, {("115", 26, "Dependence on tx.origin")}),
    ("token", 2, {("101", 36, "Integer Underflow"), ("101", 53, "Integer Overflow")}),
    ("multiowner", 2, {("106", 72, "Unprotected Selfdestruct")}),
]


@pytest.mark.parametrize(
    "name, tx_count, expected", FINDINGS, ids=[f[0] for f in FINDINGS]
)
def test_findings_unchanged_and_witnesses_replay(
    monkeypatch, generous_solver_budget, name, tx_count, expected
):
    monkeypatch.setitem(analysis_args._params, "loop_bound", 3)
    # the detectors are process singletons: an earlier analysis in this
    # process that never fired the lasers left its findings on them
    reset_callback_modules()
    runtime = load_contract(name)["runtime"]
    creation = creation_for(runtime)
    sym = SymExecWrapper(
        EVMContract(creation_code=creation, name="MAIN"),
        CLI_ADDRESS,
        "bfs",
        max_depth=128,
        execution_timeout=600,
        create_timeout=600,
        transaction_count=tx_count,
        compulsory_statespace=False,
    )
    issues = [issue.as_dict for issue in fire_lasers(sym)]
    found = {(str(i["swc-id"]), i["address"], i["title"]) for i in issues}
    assert found == expected
    # one issue per site: a site's later end states were not solved again
    assert len(issues) == len(found)
    for issue in issues:
        replayed = evm.replay(issue["tx_sequence"], creation)
        assert evm.confirms(issue, runtime, replayed) is None, issue["address"]
