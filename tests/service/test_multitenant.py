"""Multi-tenant service integration: shared device rounds, per-job
finding isolation, the result cache, and cancellation put-back.

These run REAL analyses (TEST_CFG-sized device batches on the CPU mesh);
the fast lifecycle tests live in test_scheduler.py / test_api.py.
"""

import threading
import time
from datetime import datetime
from types import SimpleNamespace

import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.laser.tpu.batch import BatchConfig
from mythril_tpu.service import AnalysisService
from mythril_tpu.service.lanes import LaneCoordinator

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


SUICIDE_SRC = """
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

ORIGIN_SRC = """
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


def contract_pair(src):
    runtime = assemble(src).hex()
    n = len(runtime) // 2
    creation = (
        assemble(
            f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\nPUSH2 {n}\n"
            "PUSH1 0x00\nRETURN\ncode:"
        ).hex()
        + runtime
    )
    return runtime, creation


def test_coresident_jobs_share_batch_and_split_findings(generous_solver_budget):
    """The tentpole acceptance path: two concurrent jobs must land in
    the SAME device batch (witnessed on the job_id plane census), their
    findings must split exactly per job, and a resubmission must answer
    from cache in under 1% of the cold wall with identical findings."""
    backend.warmup_device(TEST_CFG)
    r1, c1 = contract_pair(SUICIDE_SRC)
    r2, c2 = contract_pair(ORIGIN_SRC)
    # the window a round waits for the other job's frontier: a round
    # stops waiting as soon as every active job is pending, so a wide
    # window only costs time when the other job has no round left, and
    # a narrow one lets a loaded machine run the two jobs apart
    service = AnalysisService(workers=2, batch_cfg=TEST_CFG, gather_window_s=10.0)
    try:
        t0 = time.time()
        j1 = service.submit(r1, c1, tx_count=1, timeout=900, name="suicidal")
        j2 = service.submit(r2, c2, tx_count=1, timeout=900, name="tx-origin")
        assert service.wait(j1, 900) and service.wait(j2, 900)
        cold_wall = time.time() - t0
        res1, res2 = service.result(j1), service.result(j2)
        assert service.status(j1)["state"] == "done", service.status(j1)
        assert service.status(j2)["state"] == "done", service.status(j2)

        # per-job findings, no cross-talk between tenants
        assert "106" in res1["swc_ids"], res1["swc_ids"]
        assert "115" in res2["swc_ids"], res2["swc_ids"]
        assert "115" not in res1["swc_ids"] and "106" not in res2["swc_ids"]
        # reports carry the user-facing name, not the internal tenancy one
        assert all(i["contract"] == "suicidal" for i in res1["issues"])
        assert all(i["contract"] == "tx-origin" for i in res2["issues"])

        # >= 2 jobs were resident in one device batch (job_id plane)
        stats = service.stats()
        assert stats["max_resident_jobs"] >= 2, stats
        assert stats["shared_rounds"] >= 1, stats

        # warm resubmission: < 1% of cold wall, identical findings
        t0 = time.time()
        j3 = service.submit(r1, c1, tx_count=1, timeout=900, name="suicidal")
        assert service.wait(j3, 30)
        warm_wall = time.time() - t0
        assert service.status(j3)["cache_hit"]
        assert warm_wall < 0.01 * cold_wall, (warm_wall, cold_wall)
        res3 = service.result(j3)
        assert res3["swc_ids"] == res1["swc_ids"]
        assert res3["issues"] == res1["issues"]
    finally:
        service.shutdown(wait=True, timeout=30)


def test_cancel_running_job_leaves_singletons_clean():
    """Cancelling a RUNNING job must stop it promptly AND must not
    corrupt the process singletons for later jobs: the next submission
    of a different contract still reports its own findings."""
    backend.warmup_device(TEST_CFG)
    r1, c1 = contract_pair(SUICIDE_SRC)
    r2, c2 = contract_pair(ORIGIN_SRC)
    service = AnalysisService(workers=1, batch_cfg=TEST_CFG, gather_window_s=0.1)
    try:
        victim = service.submit(r1, c1, tx_count=3, timeout=600, name="victim")
        deadline = time.time() + 60
        while time.time() < deadline:
            if service.status(victim)["state"] == "running":
                break
            time.sleep(0.01)
        assert service.status(victim)["state"] == "running"
        assert service.cancel(victim)
        assert service.wait(victim, 120)
        assert service.status(victim)["state"] == "cancelled"
        assert service.result(victim) is None

        follower = service.submit(r2, c2, tx_count=1, timeout=120, name="after")
        assert service.wait(follower, 300)
        res = service.result(follower)
        assert "115" in res["swc_ids"], res["swc_ids"]
        # nothing of the cancelled victim leaked into the follower
        assert all(i["contract"] == "after" for i in res["issues"])
    finally:
        service.shutdown(wait=True, timeout=30)


def test_host_loop_cancellation_puts_state_back():
    """svm.exec: a cancelled job's selected state returns to the work
    list (same put-back semantics as a timeout), never dropped."""
    from tests.laser.test_bridge import BRANCH_STORE_SRC, deploy, message_state

    laser, ws, account = deploy(BRANCH_STORE_SRC)
    gs = message_state(ws, account)
    laser.work_list.append(gs)
    laser.time = datetime.now()
    laser.job_ctx = SimpleNamespace(cancelled=lambda: True, job_id=1)
    assert laser.exec() is None
    assert gs in laser.work_list


def test_cancelled_round_request_returns_none_quickly():
    """LaneCoordinator invariant I4: a request whose cancel event is
    already set comes back None (caller puts states back) without
    waiting on a device round."""
    host_lock = threading.RLock()
    coordinator = LaneCoordinator(TEST_CFG, host_lock, gather_window_s=0.05)
    coordinator.job_started()
    cancel = threading.Event()
    cancel.set()
    host_lock.acquire()
    try:
        t0 = time.time()
        result = coordinator.run_round(
            job_id=1,
            states=[object()],
            host_ops=set(),
            tape_replayers={},
            value_replayers={},
            prune_revert=True,
            deadline=None,
            cancel_event=cancel,
        )
    finally:
        host_lock.release()
        coordinator.job_finished()
    assert result is None
    assert time.time() - t0 < 5.0
    assert coordinator.rounds == 0  # no device round ran
