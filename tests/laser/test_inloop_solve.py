"""In-loop SAT pruning (laser/tpu/inloop_solve.py, ISSUE 19): the
propagation kernel's R1/R3 syntactic rules and clause-pool unit
propagation, the solver_cache pool round-trip (note_path_literal +
record -> build_inloop_pool), the mid-super-round kill through the
fused megakernel, and the ON/OFF equivalence of the full pipeline.

scripts/check.sh runs the fast half (`-k "not equivalence and not
mesh"`); the full-pipeline equivalence tests ride the full suite.
"""

import numpy as np
import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.laser.tpu import inloop_solve, megakernel, symtape, transfer
from mythril_tpu.laser.tpu.batch import (
    RUNNING,
    STOPPED,
    BatchConfig,
    append_node,
    batch_shapes,
    default_env,
    empty_batch,
    load_lane,
    make_code_bank,
)
from mythril_tpu.laser.tpu.solver_cache import GLOBAL, UNSAT

CFG = BatchConfig(lanes=4, stack_slots=8, memory_bytes=128,
                  calldata_bytes=32, storage_slots=4, code_len=64,
                  tape_slots=16, path_slots=8, mem_sym_slots=2)


def _zeros_batch(cfg=CFG):
    return {f: np.zeros(s, d) for f, (s, d) in batch_shapes(cfg).items()}


def _contradiction_batch():
    """Lanes: 0 = R1 (x and not-x), 1 = R3 (u and ISZERO(u), same sign),
    2 = single positive literal x (feasible alone), 3 = empty path.
    Returns (np_batch, h1, h2) with (h1, h2) the content hash of x."""
    nb = _zeros_batch()
    for lane in range(3):
        append_node(nb, lane, symtape.OP_CALLER)
    nb["alive"][:] = True
    nb["status"][:] = RUNNING
    nb["path_id"][0, 0] = 1
    nb["path_sign"][0, 0] = True
    nb["path_id"][0, 1] = 1
    nb["path_sign"][0, 1] = False
    nb["path_len"][0] = 2
    i2 = append_node(nb, 1, symtape.OP_ISZERO, 1, 0)
    nb["path_id"][1, 0] = 1
    nb["path_sign"][1, 0] = True
    nb["path_id"][1, 1] = i2
    nb["path_sign"][1, 1] = True
    nb["path_len"][1] = 2
    nb["path_id"][2, 0] = 1
    nb["path_sign"][2, 0] = True
    nb["path_len"][2] = 1
    return nb, int(nb["tape_h1"][2, 0]), int(nb["tape_h2"][2, 0])


def test_unsat_mask_r1_r3_fire_with_empty_pool():
    nb, _, _ = _contradiction_batch()
    st = transfer.batch_to_device(nb, CFG)
    m = np.asarray(inloop_solve.unsat_mask(inloop_solve.empty_pool(), st))
    # R1 and R3 are syntactic: no clauses needed; the lone positive
    # literal and the empty path are NOT provably UNSAT
    assert m.tolist() == [True, True, False, False]


def test_unsat_mask_only_running_lanes_eligible():
    nb, _, _ = _contradiction_batch()
    nb["status"][0] = STOPPED  # halted: the host's to lift, never killed here
    nb["alive"][1] = False
    st = transfer.batch_to_device(nb, CFG)
    m = np.asarray(inloop_solve.unsat_mask(inloop_solve.empty_pool(), st))
    assert not m.any()


def test_unsat_mask_clause_pool_direct_falsification():
    nb, h1, h2 = _contradiction_batch()
    st = transfer.batch_to_device(nb, CFG)
    # the host proved {x} UNSAT; its negated clause is the unit {~x},
    # falsified by lane 2's positive assertion of x
    pool = inloop_solve.make_pool([h1], [h2], [[0]], [[True]], [[True]])
    m = np.asarray(inloop_solve.unsat_mask(pool, st))
    assert m.tolist() == [True, True, True, False]


def test_unsat_mask_unit_propagation_chain():
    nb, h1, h2 = _contradiction_batch()
    st = transfer.batch_to_device(nb, CFG)
    # clauses (~x | y) and (~y): lane 2 asserts only x, so the kill
    # needs a propagation hop (x forces y, y falsifies the second
    # clause). A var never asserted by any lane (y) must be inferable.
    pool = inloop_solve.make_pool(
        [h1, 123], [h2, 456],
        [[0, 1], [1, 0]],
        [[True, False], [True, False]],
        [[True, True], [True, False]],
    )
    m = np.asarray(inloop_solve.unsat_mask(pool, st))
    assert m.tolist() == [True, True, True, False]


def test_solver_cache_pool_round_trip_and_stable_shape():
    """note_path_literal + a recorded must-UNSAT set compile into a
    full-capacity pool whose clause kills the matching lane."""
    nb, h1, h2 = _contradiction_batch()
    st = transfer.batch_to_device(nb, CFG)
    GLOBAL.reset()
    try:
        # no facts yet: still full-capacity (stable megakernel shape),
        # all clause slots inert
        pool0 = GLOBAL.build_inloop_pool()
        assert pool0.var_h1.shape == (inloop_solve.POOL_VARS,)
        assert pool0.lit_var.shape == (
            inloop_solve.POOL_CLAUSES, inloop_solve.POOL_WIDTH
        )
        assert not np.asarray(pool0.lit_used).any()
        m0 = np.asarray(inloop_solve.unsat_mask(pool0, st))
        assert m0.tolist() == [True, True, False, False]

        # the bridge registers the literal identity at lift time; a host
        # decider then records the set {x} as must-UNSAT
        GLOBAL.note_path_literal(uid=7001, h1=h1, h2=h2, sign=True)
        GLOBAL.record((), UNSAT, key=frozenset({7001}), digest=b"t19")
        pool = GLOBAL.build_inloop_pool()
        assert pool.var_h1.shape == pool0.var_h1.shape  # no recompile
        assert np.asarray(pool.lit_used).sum() == 1
        m = np.asarray(inloop_solve.unsat_mask(pool, st))
        assert m.tolist() == [True, True, True, False]

        # a set touching an unregistered term is skipped (stays
        # host-only), never guessed at
        GLOBAL.record((), UNSAT, key=frozenset({7001, 9999}), digest=b"t19b")
        pool2 = GLOBAL.build_inloop_pool()
        assert np.asarray(pool2.lit_used).sum() == 1
    finally:
        GLOBAL.reset()


LOOP_SRC = "here:\nJUMPDEST\nPUSH1 :here\nJUMP"


def _looping_pair(with_contradiction=True):
    cfg = BatchConfig(lanes=4, stack_slots=32, memory_bytes=1024,
                      calldata_bytes=128, storage_slots=8, code_len=512)
    cb = make_code_bank([assemble(LOOP_SRC)], cfg.code_len)
    st = empty_batch(cfg)
    for lane in range(2):
        st = load_lane(st, lane, calldata=b"", gas=10_000_000)
    if with_contradiction:
        pid = np.asarray(st.path_id).copy()
        psn = np.asarray(st.path_sign).copy()
        pln = np.asarray(st.path_len).copy()
        top = np.asarray(st.tape_op).copy()
        th1 = np.asarray(st.tape_h1).copy()
        th2 = np.asarray(st.tape_h2).copy()
        tln = np.asarray(st.tape_len).copy()
        top[0, 0] = symtape.OP_CALLER
        h1, h2 = symtape.node_hash(symtape.OP_CALLER, 0, 0,
                                   np.zeros(16, np.uint32), xp=np)
        th1[0, 0], th2[0, 0] = h1, h2
        tln[0] = 1
        pid[0, 0], psn[0, 0] = 1, True
        pid[0, 1], psn[0, 1] = 1, False
        pln[0] = 2
        st = st._replace(path_id=pid, path_sign=psn, path_len=pln,
                         tape_op=top, tape_h1=th1, tape_h2=th2, tape_len=tln)
    return cb, st


def test_fused_inloop_kill_does_not_end_super_round():
    """The acceptance demonstration at kernel level: a must-UNSAT fork
    (R1 contradiction) dies between rounds while its sibling keeps
    stepping to max_rounds — the kill does NOT end the super-round, and
    the dying lane folds its counters exactly like a REVERT prune."""
    cb, st = _looping_pair()
    out = megakernel.run_fused(
        cb, default_env(), st, max_rounds=3, steps_per_round=64,
        with_solve=True,
    )
    stats = megakernel.decode_info(out.info)
    assert stats.inloop_kills == 1
    assert stats.pruned_lanes == 0  # separable from static revert prune
    # the super-round survived the kill: the feasible sibling kept
    # looping through all three rounds
    assert stats.rounds == 3
    alive = np.asarray(out.st.alive)
    assert alive.sum() == 1
    assert int(np.asarray(out.st.status)[0]) == RUNNING
    assert int(np.asarray(out.st.steps)[0]) == 3 * 64
    # counter folds match the prune path: the killed lane's 64 steps
    # moved into pruned_steps and its own planes were zeroed
    assert stats.pruned_steps == 64
    assert int(np.asarray(out.st.steps)[1:].sum()) == 0
    assert np.asarray(out.pruned_visited).any()


def test_fused_kill_switch_off_leaves_fork_for_host():
    # with_solve=False is the exact pre-ISSUE-19 loop: the infeasible
    # fork rides the whole super-round and stays for the host drain
    cb, st = _looping_pair()
    out = megakernel.run_fused(
        cb, default_env(), st, max_rounds=3, steps_per_round=64,
        with_solve=False,
    )
    stats = megakernel.decode_info(out.info)
    assert stats.inloop_kills == 0
    assert np.asarray(out.st.alive).sum() == 2


def test_fused_with_solve_feasible_lanes_untouched():
    # no contradictions anywhere: ON must behave exactly like OFF
    cb, st = _looping_pair(with_contradiction=False)
    on = megakernel.run_fused(
        cb, default_env(), st, max_rounds=2, steps_per_round=64,
        with_solve=True,
    )
    cb2, st2 = _looping_pair(with_contradiction=False)
    off = megakernel.run_fused(
        cb2, default_env(), st2, max_rounds=2, steps_per_round=64,
        with_solve=False,
    )
    assert megakernel.decode_info(on.info).inloop_kills == 0
    for name in ("alive", "status", "pc", "sp", "steps", "stack"):
        assert np.array_equal(
            np.asarray(getattr(on.st, name)),
            np.asarray(getattr(off.st, name)),
        ), f"with_solve=True diverged on untouched plane {name!r}"


# -- full-pipeline ON/OFF equivalence -----------------------------------------

MESH_CFG = BatchConfig(
    lanes=16, stack_slots=16, memory_bytes=256, calldata_bytes=128,
    storage_slots=8, code_len=512, tape_slots=64, path_slots=16,
    mem_sym_slots=8,
)

KILL_SRC = """
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


def _make_creation(runtime_hex: str) -> str:
    n = len(runtime_hex) // 2
    src = (
        f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\nPUSH2 {n}\n"
        "PUSH1 0x00\nRETURN\ncode:"
    )
    return assemble(src).hex() + runtime_hex


def _analyze(src, monkeypatch, inloop: bool, tx=1, timeout=480):
    from mythril_tpu.analysis.security import fire_lasers
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.ethereum.evmcontract import EVMContract

    # small always-engage config: the production default defers the
    # device 1.5 s, which a tiny test contract never reaches
    monkeypatch.setattr(backend, "DEFAULT_BATCH_CFG", MESH_CFG)
    key = backend._warmup_key(MESH_CFG, False)
    backend._warmup_events.pop(key, None)
    backend._warmup_done.discard(key)
    monkeypatch.setenv("MYTHRIL_TPU_INLOOP_SOLVE", "1" if inloop else "0")
    GLOBAL.reset()
    runtime = assemble(src).hex()
    contract = EVMContract(
        code=runtime, creation_code=_make_creation(runtime), name="T"
    )
    sym = SymExecWrapper(
        contract,
        address=0x1234,
        strategy="tpu-batch",
        execution_timeout=timeout,
        transaction_count=tx,
        max_depth=64,
    )
    issues = sorted({(i.swc_id, i.address) for i in fire_lasers(sym)})
    strategy = backend.find_tpu_strategy(sym.laser.strategy)
    return issues, strategy


def test_equivalence_single_device_on_vs_off(monkeypatch):
    """The observable analysis result is invariant under the in-loop
    kill: identical SWC issue set ON vs OFF. A device-killed fork must
    be indistinguishable from a host filter_feasible kill."""
    issues_off, strat_off = _analyze(KILL_SRC, monkeypatch, inloop=False)
    issues_on, strat_on = _analyze(KILL_SRC, monkeypatch, inloop=True)
    assert issues_on == issues_off
    assert any(swc == "106" for swc, _ in issues_on)
    # the OFF arm cannot report in-loop kills by construction
    assert strat_off is None or strat_off.in_loop_unsat_kills == 0
    assert strat_on is not None and strat_on.device_rounds > 0


@pytest.mark.slow
def test_equivalence_virtual_mesh_on_vs_off(monkeypatch):
    import jax

    assert len(jax.devices()) == 8, "conftest should provide 8 virtual devices"
    monkeypatch.setattr(backend, "MESH_MODE", "on")
    issues_off, _ = _analyze(KILL_SRC, monkeypatch, inloop=False)
    issues_on, _ = _analyze(KILL_SRC, monkeypatch, inloop=True)
    assert issues_on == issues_off
    assert any(swc == "106" for swc, _ in issues_on)


@pytest.mark.slow
def test_equivalence_becstress_on_vs_off(monkeypatch):
    """The BENCH_r07 acceptance bar as a test: the bench stress contract
    reports the same SWC issue set with the in-loop solve ON and OFF."""
    import bench

    issues_off, _ = _analyze(
        bench.STRESS_SRC, monkeypatch, inloop=False, tx=2, timeout=120
    )
    issues_on, _ = _analyze(
        bench.STRESS_SRC, monkeypatch, inloop=True, tx=2, timeout=120
    )
    assert issues_on == issues_off
