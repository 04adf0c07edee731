"""Solidity front-end tests with a scripted `solc` (no compiler in the
image): a stand-in binary emits canned standard-json, which exercises
compilation plumbing, contract selection, source-index collection,
source-map decoding, and address -> source-line resolution.
Parity: reference mythril/ethereum/util.py + solidity/soliditycontract.py.
"""

import json
import os
import stat

import pytest

from mythril_tpu.ethereum.util import get_solc_json
from mythril_tpu.exceptions import CompilerError, NoContractFoundError
from mythril_tpu.solidity.soliditycontract import SolidityContract

SOURCE = "contract Token {\n    function f() public {}\n}\n"

# runtime: PUSH1 1 PUSH1 1 SSTORE STOP  -> 4 instructions, 6 bytes
RUNTIME = "6001600155" + "00"
# deploy: CODECOPY(dest=0, offset=12, len=6); RETURN(0, 6) — 12 bytes
CREATION = "6006600c60003960066000f3" + RUNTIME


def write_fake_solc(tmp_path, payload: dict) -> str:
    out_json = tmp_path / "out.json"
    out_json.write_text(json.dumps(payload))
    solc = tmp_path / "solc"
    solc.write_text(f"#!/bin/sh\ncat > /dev/null\ncat {out_json}\n")
    solc.chmod(solc.stat().st_mode | stat.S_IEXEC)
    return str(solc)


@pytest.fixture()
def compiled(tmp_path):
    src_file = tmp_path / "T.sol"
    src_file.write_text(SOURCE)
    src_name = str(src_file)
    payload = {
        "contracts": {
            src_name: {
                "Token": {
                    "evm": {
                        "deployedBytecode": {
                            "object": RUNTIME,
                            # one entry per instruction; f() body is the
                            # second source span
                            "sourceMap": "0:48:0:-:0;20:23:0;;",
                        },
                        "bytecode": {
                            "object": CREATION,
                            "sourceMap": "0:48:0:-:0;;;;;;;;;",
                        },
                    }
                },
                "Empty": {"evm": {"deployedBytecode": {"object": ""}}},
            }
        },
        "sources": {
            src_name: {
                "id": 0,
                "ast": {
                    "nodes": [
                        {"nodeType": "ContractDefinition", "src": "0:48:0"}
                    ]
                },
            }
        },
    }
    return src_name, write_fake_solc(tmp_path, payload)


def test_contract_selection_and_code(compiled):
    src_name, solc = compiled
    contract = SolidityContract(src_name, solc_binary=solc)
    # the empty artifact is skipped; the deployable one is chosen
    assert contract.name == "Token"
    assert contract.code == RUNTIME
    assert contract.creation_code == CREATION
    assert len(contract.mappings) == 4


def test_source_info_resolution(compiled):
    src_name, solc = compiled
    contract = SolidityContract(src_name, solc_binary=solc)
    info = contract.get_source_info(0)
    assert info.filename == src_name
    assert info.lineno == 1
    assert "contract Token" in info.code


def test_missing_contract_raises(compiled):
    src_name, solc = compiled
    with pytest.raises(NoContractFoundError):
        SolidityContract(src_name, name="Nope", solc_binary=solc)


def test_cli_analyze_solidity_file(compiled):
    """End-to-end through the orchestration layer: load_from_solidity
    honors the SOLC env override and the analysis runs on the compiled
    runtime (the stand-in contract stores a constant -> no issues, but
    the pipeline must complete and report per-contract)."""
    import subprocess
    import sys

    src_name, solc = compiled
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SOLC"] = solc
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "myth"),
            "analyze",
            src_name,
            "--no-onchain-data",
            "-t",
            "1",
            "--execution-timeout",
            "120",
            "-o",
            "json",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=repo,
        env=env,
    )
    data = json.loads(proc.stdout)
    assert data["success"] is True, proc.stderr[-500:]


def test_get_solc_json_error_paths(tmp_path):
    src = tmp_path / "E.sol"
    src.write_text(SOURCE)
    with pytest.raises(CompilerError, match="Compiler not found"):
        get_solc_json(str(src), solc_binary=str(tmp_path / "missing-solc"))
    bad = write_fake_solc(
        tmp_path,
        {
            "errors": [
                {
                    "severity": "error",
                    "formattedMessage": "E.sol:1: parse error",
                }
            ]
        },
    )
    with pytest.raises(CompilerError, match="parse error"):
        get_solc_json(str(src), solc_binary=bad)
