"""Detection parity through the tpu-batch hybrid backend.

The VERDICT round-1 gate (item 2): the detection tests must pass with the
TPU strategy selected and report the same SWC sets as the host path —
and the device must actually participate (device_rounds > 0), proving
the batched engine is wired behind the strategy boundary
(reference seam: mythril/laser/ethereum/strategy/__init__.py:6).
"""

import logging

import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu.analysis.security import fire_lasers
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.laser.tpu.batch import BatchConfig
from mythril_tpu.laser.tpu.backend import find_tpu_strategy

logging.getLogger().setLevel(logging.ERROR)

# small lanes keep CPU compile time down; one shared config = one compile
TEST_CFG = BatchConfig(
    lanes=32,
    stack_slots=16,
    memory_bytes=256,
    calldata_bytes=128,
    storage_slots=8,
    code_len=512,
    tape_slots=64,
    path_slots=16,
    mem_sym_slots=8,
)


@pytest.fixture(autouse=True)
def small_batch(monkeypatch):
    monkeypatch.setattr(backend, "DEFAULT_BATCH_CFG", TEST_CFG)


def make_creation(runtime_hex: str) -> str:
    n = len(runtime_hex) // 2
    src = (
        f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\nPUSH2 {n}\n"
        "PUSH1 0x00\nRETURN\ncode:"
    )
    return assemble(src).hex() + runtime_hex


def analyze_tpu(runtime_src: str, tx_count=1, timeout=120, max_depth=64):
    runtime = assemble(runtime_src).hex()
    contract = EVMContract(
        code=runtime, creation_code=make_creation(runtime), name="T"
    )
    sym = SymExecWrapper(
        contract,
        address=0x1234,
        strategy="tpu-batch",
        execution_timeout=timeout,
        transaction_count=tx_count,
        max_depth=max_depth,
    )
    strategy = find_tpu_strategy(sym.laser.strategy)
    return fire_lasers(sym), strategy


def swc_ids(issues):
    return {i.swc_id for i in issues}


def test_swc106_suicide_parity_and_device_participation():
    issues, strategy = analyze_tpu(
        """
        PUSH1 0x00
        CALLDATALOAD
        PUSH1 0xe0
        SHR
        PUSH4 0xdeadbeef
        EQ
        PUSH2 :kill
        JUMPI
        STOP
        kill:
        JUMPDEST
        CALLER
        SELFDESTRUCT
        """
    )
    assert "106" in swc_ids(issues)
    # witness transaction parity with the host path
    issue = [i for i in issues if i.swc_id == "106"][0]
    steps = issue.transaction_sequence["steps"]
    assert steps[-1]["input"].startswith("0xdeadbeef")
    # the device actually ran lanes for this analysis
    assert strategy.device_rounds > 0
    assert strategy.device_steps_retired > 0


def test_swc115_origin_parity():
    issues, strategy = analyze_tpu(
        """
        ORIGIN
        PUSH20 0xaffeaffeaffeaffeaffeaffeaffeaffeaffeaffe
        EQ
        PUSH2 :ok
        JUMPI
        STOP
        ok:
        JUMPDEST
        PUSH1 0x01
        PUSH1 0x00
        SSTORE
        STOP
        """
    )
    assert "115" in swc_ids(issues)
    assert strategy.device_rounds > 0


def test_swc110_assert_parity():
    issues, strategy = analyze_tpu(
        """
        PUSH1 0x00
        CALLDATALOAD
        PUSH1 0x2a
        EQ
        PUSH2 :boom
        JUMPI
        STOP
        boom:
        JUMPDEST
        ASSERT_FAIL
        """
    )
    assert "110" in swc_ids(issues)
    assert strategy.device_rounds > 0


def test_swc101_integer_overflow_parity():
    issues, strategy = analyze_tpu(
        """
        PUSH1 0x04
        CALLDATALOAD
        PUSH32 0xffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff00
        ADD
        PUSH1 0x00
        SSTORE
        STOP
        """
    )
    assert "101" in swc_ids(issues)
    assert strategy.device_rounds > 0


def test_swc105_ether_thief_parity(generous_solver_budget):
    issues, strategy = analyze_tpu(
        """
        PUSH1 0x00
        PUSH1 0x00
        PUSH1 0x00
        PUSH1 0x00
        SELFBALANCE
        PUSH1 0x04
        CALLDATALOAD
        PUSH2 0x8fc
        CALL
        POP
        STOP
        """,
        timeout=900,
    )
    assert "105" in swc_ids(issues)


def test_clean_contract_no_false_positive():
    issues, strategy = analyze_tpu(
        """
        CALLER
        PUSH20 0xaffeaffeaffeaffeaffeaffeaffeaffeaffeaffe
        EQ
        PUSH2 :ok
        JUMPI
        PUSH1 0x00
        PUSH1 0x00
        REVERT
        ok:
        JUMPDEST
        CALLER
        SELFDESTRUCT
        """
    )
    assert "106" not in swc_ids(issues)
    assert strategy.device_rounds > 0


# a loop whose trip count is calldata-controlled: every iteration forks on
# the symbolic JUMPI, so exploration is unbounded without the loop-bound
LOOPY_SRC = """
PUSH1 0x00
loop:
JUMPDEST
PUSH1 0x01
ADD
DUP1
PUSH1 0x00
CALLDATALOAD
GT
PUSH2 :loop
JUMPI
PUSH1 0x00
SSTORE
STOP
"""


def _analyze_loopy(loop_bound):
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    runtime = assemble(LOOPY_SRC).hex()
    contract = EVMContract(
        code=runtime, creation_code=make_creation(runtime), name="T"
    )
    sym = SymExecWrapper(
        contract,
        address=0x1234,
        strategy="tpu-batch",
        execution_timeout=120,
        transaction_count=1,
        max_depth=512,
        loop_bound=loop_bound,
    )
    strategy = find_tpu_strategy(sym.laser.strategy)
    return sym.laser, strategy


def test_loop_bound_respected_under_tpu_batch():
    """-b bounds device-explored loops (VERDICT r2 weak #4): the jumpdest
    traces carried back from device lanes feed BoundedLoopsStrategy, which
    must actually DROP states when the ring shows too many cycle repeats."""
    laser, strat = _analyze_loopy(loop_bound=2)
    assert strat.device_rounds > 0
    from mythril_tpu.laser.evm.strategy.extensions.bounded_loops import (
        BoundedLoopsStrategy,
    )

    bounded = laser.strategy
    while not isinstance(bounded, BoundedLoopsStrategy):
        bounded = bounded.super_strategy
    assert bounded.skipped > 0


def test_device_steps_count_toward_depth():
    """Device-retired instructions increment mstate.depth (VERDICT r2
    weak #4): with max_depth well below the loop's step count, tpu-batch
    terminates by depth rather than running to the device step budget."""
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    runtime = assemble(LOOPY_SRC).hex()
    contract = EVMContract(
        code=runtime, creation_code=make_creation(runtime), name="T"
    )
    sym = SymExecWrapper(
        contract,
        address=0x1234,
        strategy="tpu-batch",
        execution_timeout=120,
        transaction_count=1,
        max_depth=48,
        loop_bound=100,  # loop bound out of the way: depth must do the bounding
    )
    strategy = find_tpu_strategy(sym.laser.strategy)
    assert strategy.device_rounds > 0
    # exploration terminated (no runaway states) under the small depth cap
    assert sym.laser.total_states < 5000


def test_coverage_parity_host_vs_tpu_batch():
    """The coverage plugin's per-bytecode bitmap includes device-retired
    instructions (VERDICT r2 weak #5)."""
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    src = """
    PUSH1 0x00
    CALLDATALOAD
    PUSH2 :a
    JUMPI
    PUSH1 0x01
    PUSH1 0x00
    SSTORE
    STOP
    a:
    JUMPDEST
    PUSH1 0x02
    PUSH1 0x00
    SSTORE
    STOP
    """
    runtime = assemble(src).hex()

    def coverage_for(strategy_name):
        contract = EVMContract(
            code=runtime, creation_code=make_creation(runtime), name="T"
        )
        sym = SymExecWrapper(
            contract,
            address=0x1234,
            strategy=strategy_name,
            execution_timeout=120,
            transaction_count=1,
            max_depth=64,
        )
        # the coverage plugin was loaded by the wrapper; find its bitmap
        cov = {}
        for code, (total, bitmap) in _last_coverage_plugin(sym).coverage.items():
            if code == runtime:
                cov[code] = (total, sum(bitmap))
        return cov.get(runtime)

    host = coverage_for("bfs")
    device = coverage_for("tpu-batch")
    assert host is not None and device is not None
    assert device == host


def _last_coverage_plugin(sym):
    from mythril_tpu.laser.evm.plugins.implementations.coverage.coverage_plugin import (
        InstructionCoveragePlugin,
    )

    for hook in sym.laser._stop_sym_exec_hooks:
        closure = getattr(hook, "__closure__", None) or ()
        for cell in closure:
            if isinstance(cell.cell_contents, InstructionCoveragePlugin):
                return cell.cell_contents
    # the plugin closes over `self` implicitly via bound method cells; fall
    # back to scanning the execute_state hooks
    for hook in sym.laser._execute_state_hooks:
        closure = getattr(hook, "__closure__", None) or ()
        for cell in closure:
            if isinstance(cell.cell_contents, InstructionCoveragePlugin):
                return cell.cell_contents
    raise AssertionError("coverage plugin not found on the laser hooks")
