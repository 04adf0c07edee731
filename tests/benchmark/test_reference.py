"""The plain reference: Keccak-256, the concrete EVM, and the witness
oracles, on hand-made witnesses and on outputs the program produced."""

import copy
import json
import os

import pytest

from benchmark.lib import evm
from benchmark.lib.check import Analysis, judge, passed
from benchmark.lib.keccak import keccak256
from benchmark.lib.redeploy import Deployment, creation_for, load_contract

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CREATOR = "0x" + "affe" * 10
ATTACKER = "0x" + "deadbeef" * 5
CALLEE = "0x901d12ebe1b195e5aa8748e62bd7734ae19b51f"


def recorded():
    with open(os.path.join(DATA, "recorded_outputs.json")) as fh:
        return json.load(fh)


def test_keccak256_vectors():
    assert keccak256(b"").hex() == "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    assert keccak256(b"abc").hex() == "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    # more than one 136-byte block
    assert keccak256(b"a" * 200).hex() == keccak256(b"a" * 200).hex()
    assert keccak256(b"a" * 135) != keccak256(b"a" * 136)


def _witness(creation, calls):
    steps = [{"input": "0x" + creation, "value": "0x0", "origin": CREATOR, "address": ""}]
    for sender, calldata in calls:
        steps.append({"input": "0x" + calldata, "value": "0x0", "origin": sender, "address": CALLEE})
    return {"initialState": {"accounts": {}}, "steps": steps}


def _confirms(contract, swc, pc, calls):
    runtime = load_contract(contract)["runtime"]
    creation = creation_for(runtime)
    rep = evm.replay(_witness(creation, calls), creation)
    return evm.confirms({"swc-id": swc, "address": pc}, runtime, rep)


def _word(v):
    return "%064x" % v


def test_documented_hazards_replay_on_hand_made_witnesses():
    assert _confirms("killable", "106", 19, [(ATTACKER, "41c0e1b5")]) is None
    assert _confirms("killable", "106", 19, [(CREATOR, "41c0e1b5")]) is not None
    assert _confirms("multiowner", "106", 72, [(ATTACKER, "90c3f38f"), (ATTACKER, "41c0e1b5")]) is None
    assert _confirms("multiowner", "106", 72, [(ATTACKER, "41c0e1b5")]) is not None
    assert _confirms("originauth", "115", 26, [(ATTACKER, "")]) is None
    # token: a sender with no balance sends 1 (underflow), and credits 1 to a full balance
    assert _confirms("token", "101", 36, [(ATTACKER, "a9059cbb" + _word(7) + _word(1))]) is None
    assert _confirms("token", "101", 36, [(ATTACKER, "a9059cbb" + _word(7) + _word(0))]) is not None
    big = (1 << 256) - 1
    assert _confirms("token", "101", 53, [(ATTACKER, "a9059cbb" + _word(int(ATTACKER, 16)) + _word(big)),
                                         (ATTACKER, "a9059cbb" + _word(int(ATTACKER, 16)) + _word(1))]) is None
    # BECToken: cnt * value wraps (2 receivers, value 2**255)
    batch = "83f12fec" + _word(0x40) + _word(1 << 255) + _word(2) + _word(1) + _word(2)
    assert _confirms("bectoken", "101", 28, [(ATTACKER, batch)]) is None
    no_wrap = "83f12fec" + _word(0x40) + _word(1) + _word(2) + _word(1) + _word(2)
    assert _confirms("bectoken", "101", 28, [(ATTACKER, no_wrap)]) is not None


@pytest.mark.parametrize("excess", [1, 3])
def test_a_wrap_in_a_transaction_that_reverts_is_not_confirmed(excess):
    """cnt * value wraps to a small amount that the sender's empty balance
    cannot cover: the MUL wrapped, and then the transaction reverted."""
    value = (1 << 255) + excess
    batch = "83f12fec" + _word(0x40) + _word(value) + _word(2) + _word(1) + _word(2)
    runtime = load_contract("bectoken")["runtime"]
    creation = creation_for(runtime)
    rep = evm.replay(_witness(creation, [(ATTACKER, batch)]), creation)
    (_, trace), = rep.calls
    assert 28 in trace.wrapped and trace.reverted
    assert "did not revert" in evm.confirms({"swc-id": "101", "address": 28}, runtime, rep)


def test_an_issue_at_the_wrong_pc_or_of_an_unknown_class_is_not_confirmed():
    assert "not SELFDESTRUCT" in _confirms("killable", "106", 18, [(ATTACKER, "41c0e1b5")])
    assert "no oracle" in _confirms("killable", "999", 19, [(ATTACKER, "41c0e1b5")])
    with pytest.raises(evm.ReplayError):
        runtime = load_contract("killable")["runtime"]
        evm.replay(_witness(creation_for(runtime), []), creation_for(runtime))


def _analyses(outputs):
    return [Analysis(Deployment(o["contract"], o["name"], o["creation"], o["runtime"]),
                     o["tx_count"], copy.deepcopy(o["issues"]), device_steps=o["device_steps"])
            for o in outputs]


def test_the_programs_recorded_outputs_are_correct():
    checks = judge(_analyses(recorded()), device_required=True)
    assert passed(checks), checks
    assert set(checks) == {"missing_hazards", "unconfirmed_witnesses", "failed_analyses", "host_only_analyses"}


def test_a_missing_hazard_and_a_failed_analysis_are_counted():
    outputs = recorded()
    analyses = _analyses(outputs)
    analyses[0].issues = [i for i in analyses[0].issues if i["address"] != 53]
    analyses[1].error = "boom"
    checks = judge(analyses, device_required=False)
    assert checks["missing_hazards"]["value"] == 1
    assert checks["failed_analyses"]["value"] == 1
    assert "host_only_analyses" not in checks
