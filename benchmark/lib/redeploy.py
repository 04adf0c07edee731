"""Seeded redeploys of the benchmark's contracts.

A copy of the program's chain-scan generator (mythril_tpu/fleet/
ingest.py ``mutate_deployment``): each deployment is a contract with a
fresh Solidity metadata trailer appended to its runtime, and its
creation wrapper rebuilt. The trailer gives the code a new keccak, so no
result cache or per-code memo answers it, while the analysis is the same
because the disassembler strips metadata as it does for compiler output.

The contracts are the benchmark's own copies (``benchmark/contracts/
<name>.json``): runtime bytecode plus the hazards its source documents.
"""

import json
import os
import random
from typing import Dict, NamedTuple

CONTRACTS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "contracts")

# swarm-hash metadata trailer: 0xa1 0x65 'bzzr0' 0x58 0x20 <32 bytes> 0x0029
_METADATA_PREFIX = "a165627a7a72305820"
_METADATA_SUFFIX = "0029"


class Deployment(NamedTuple):
    contract: str  # name of the file under benchmark/contracts
    name: str  # the deployment's own name
    creation: str  # hex
    runtime: str  # hex


def load_contract(name: str, root: str = CONTRACTS_DIR) -> Dict:
    with open(os.path.join(root, name + ".json")) as fh:
        return json.load(fh)


def creation_for(runtime_hex: str) -> str:
    """CODECOPY/RETURN deploy code in front of a runtime blob:
    PUSH2 n PUSH2 15 PUSH1 0 CODECOPY PUSH2 n PUSH1 0 RETURN <runtime>."""
    n = len(runtime_hex) // 2
    if n > 0xFFFF:
        raise ValueError("runtime too long for a PUSH2 length")
    return "61%04x61000f60003961%04x6000f3" % (n, n) + runtime_hex


def redeploy(contract: str, runtime_hex: str, rng: random.Random, serial: int) -> Deployment:
    trailer = (
        _METADATA_PREFIX
        + "".join("%02x" % rng.randrange(256) for _ in range(32))
        + _METADATA_SUFFIX
    )
    runtime = runtime_hex + trailer
    return Deployment(contract, "%s-%04d" % (contract, serial), creation_for(runtime), runtime)
