"""Scheduler-side robustness, pipeline stubbed: crash isolation with
structured error reports, the retry-once-then-quarantine ladder,
shutdown draining/deadline semantics, and the job-context leak
regression. Real-pipeline fault runs live in test_fault_matrix.py."""

import threading
import time
from types import SimpleNamespace

import pytest

from mythril_tpu.laser.tpu import solver_cache
from mythril_tpu.robustness import faults
from mythril_tpu.service import AdmissionError, AnalysisService
from mythril_tpu.service.cache import QUARANTINE_AFTER, cache_key

DUMMY_CFG = SimpleNamespace(lanes=8)


class FakeLaser:
    """Just enough laser surface for pre_exec_hook consumers: the
    checkpoint journal (register_laser_hooks) and the strategy-counter
    harvest (strategy, executed_transaction_rounds)."""

    def __init__(self):
        self.strategy = None  # find_tpu_strategy(None) -> None
        self.executed_transaction_rounds = 0
        self.open_states = []
        self._stop_hooks = []

    def register_laser_hooks(self, kind, hook):
        self._stop_hooks.append(hook)


class StubSymExec:
    """SymExecWrapper stand-in: drives pre_exec_hook like the real one,
    then runs a per-test script (rounds to 'execute', whether to raise)."""

    script = {"rounds": 0, "raise_after": None, "frontier": ["s0"]}
    seen = []

    def __init__(self, contract, pre_exec_hook=None, resume_from=None, **kw):
        type(self).seen.append({"contract": contract, "resume": resume_from})
        laser = FakeLaser()
        if resume_from is not None:
            laser.executed_transaction_rounds = resume_from.rounds_done
            laser.open_states = resume_from.restore()
        if pre_exec_hook is not None:
            pre_exec_hook(laser)
        script = type(self).script
        for _ in range(script["rounds"]):
            laser.executed_transaction_rounds += 1
            laser.executed_transaction_address = 0x1234
            # a crash mid-round precedes the round's stop hooks, so the
            # round that crashed is never journaled (real svm ordering)
            if script["raise_after"] == laser.executed_transaction_rounds:
                raise faults.InjectedCrash(
                    "boom", seam="scheduler_worker", kind="crash"
                )
            laser.open_states = list(script["frontier"])
            for hook in laser._stop_hooks:
                hook()


@pytest.fixture
def stub_pipeline(monkeypatch):
    import mythril_tpu.analysis.security as security
    import mythril_tpu.analysis.symbolic as symbolic
    import mythril_tpu.ethereum.evmcontract as evmcontract

    StubSymExec.script = {"rounds": 0, "raise_after": None, "frontier": ["s0"]}
    StubSymExec.seen = []
    monkeypatch.setattr(symbolic, "SymExecWrapper", StubSymExec)
    monkeypatch.setattr(
        evmcontract, "EVMContract",
        lambda code, creation_code, name: SimpleNamespace(
            code=code, creation_code=creation_code, name=name
        ),
    )
    monkeypatch.setattr(
        security, "fire_lasers_for_job", lambda sym, names, modules: []
    )
    return StubSymExec


@pytest.fixture
def service():
    svc = AnalysisService(workers=1, queue_size=8, batch_cfg=DUMMY_CFG)
    yield svc
    svc.shutdown(wait=True, timeout=10)


def wait_for(predicate, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.005)
    return False


# -- crash isolation + quarantine ------------------------------------------


def test_poison_job_fails_with_report_worker_survives(stub_pipeline, service):
    """A deterministically-crashing job fails ONLY itself — with a
    structured report — and its two strikes quarantine the code hash;
    the worker then completes the next (stubbed-clean) job."""
    faults.configure("scheduler_worker=crash:match=poison")
    poison = service.submit("60ff", tx_count=1, name="poison-pill")
    assert service.wait(poison, 20)
    status = service.status(poison)
    assert status["state"] == "failed"
    report = status["error_report"]
    assert report["exception"] == "InjectedCrash"
    assert report["seam"] == "scheduler_worker"
    assert report["kind"] == "crash"
    assert report["attempt"] == 1        # crashed twice: 0 then the retry
    assert status["retried"] and status["degraded"]

    # both strikes landed -> quarantined at admission, citing the report
    with pytest.raises(AdmissionError, match="quarantined"):
        service.submit("60ff", tx_count=1, name="poison-pill")
    assert service.stats()["quarantined_jobs"] == 1

    # the worker survived: a different contract completes normally
    ok = service.submit("6001", tx_count=1, name="benign")
    assert service.wait(ok, 20)
    assert service.status(ok)["state"] == "done"
    assert service.stats()["jobs_failed"] == 1

    # an operator can lift the ban
    assert service.cache.lift_quarantine(cache_key("", "60ff"))
    faults.configure(None)
    again = service.submit("60ff", tx_count=1, name="poison-pill")
    assert service.wait(again, 20)
    assert service.status(again)["state"] == "done"


def test_device_warmup_failure_fails_job_without_strike(
    stub_pipeline, service, monkeypatch
):
    """A job whose device kernels cannot warm up fails with that error.
    The contract is not at fault: no retry, and no strike toward
    quarantine."""
    import mythril_tpu.analysis.symbolic as symbolic
    from mythril_tpu.exceptions import DeviceWarmupError

    def no_device(*args, **kw):
        raise DeviceWarmupError("device warmup failed on tpu: compile error")

    monkeypatch.setattr(symbolic, "SymExecWrapper", no_device)
    job = service.submit("600d", tx_count=1, name="needs-device")
    assert service.wait(job, 20)
    status = service.status(job)
    assert status["state"] == "failed"
    assert "device warmup failed" in status["error"]
    assert not status["retried"]
    assert not service.cache.is_quarantined(cache_key("", "600d"))
    assert service.stats()["jobs_failed"] == 1


def test_transient_crash_retries_once_and_clears_strikes(
    stub_pipeline, service
):
    """One injected crash -> the retry succeeds -> DONE with
    degraded/retried flags, and the success wipes the strike so the
    hash never drifts toward quarantine across submissions."""
    faults.configure("scheduler_worker=crash:n=1")
    job = service.submit("6002", tx_count=1, name="flaky")
    assert service.wait(job, 20)
    status = service.status(job)
    assert status["state"] == "done"
    assert status["retried"] and status["degraded"]
    assert service.result(job)["retried"]
    assert service.stats()["jobs_retried"] == 1
    assert not service.cache.is_quarantined(cache_key("", "6002"))
    assert service.cache._crash_strikes == {}


def test_retry_resumes_from_latest_checkpoint(stub_pipeline, service):
    """A crash mid-analysis retries from the journaled frontier: the
    second attempt starts at the checkpoint's round, not from scratch."""
    stub_pipeline.script = {
        "rounds": 3, "raise_after": 2, "frontier": ["after-round"]
    }
    faults.configure(None)
    job = service.submit("6003", tx_count=3, name="resumable")
    assert service.wait(job, 20)
    # attempt 0 journaled round 1 (round 2 crashed mid-flight), so the
    # retry was handed the round-1 checkpoint...
    assert len(stub_pipeline.seen) == 2
    resume = stub_pipeline.seen[1]["resume"]
    assert resume is not None and resume.rounds_done == 1
    assert resume.restore() == ["after-round"]
    # ...but crashes again at absolute round 2 (raise_after is absolute
    # because the offset keeps numbering absolute), so the job fails
    # with both strikes recorded
    assert service.status(job)["state"] == "failed"
    assert service.status(job)["error_report"]["round"] == 2
    assert service.cache.is_quarantined(cache_key("", "6003"))


def test_scheduler_internal_failure_isolated(stub_pipeline, service):
    """Even a crash OUTSIDE _run_attempt's classification (scheduler
    plumbing itself) fails only the job; the worker survives."""
    original = service.journal.clear
    calls = {"n": 0}

    def exploding_clear(job_id):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("plumbing burst")
        return original(job_id)

    service.journal.clear = exploding_clear
    job = service.submit("6004", tx_count=1, name="unlucky")
    assert service.wait(job, 20)
    assert service.status(job)["state"] == "failed"
    assert "plumbing burst" in service.status(job)["error"]
    ok = service.submit("6005", tx_count=1, name="next")
    assert service.wait(ok, 20)
    assert service.status(ok)["state"] == "done"


# -- shutdown semantics (satellite) ----------------------------------------


def test_shutdown_drains_queue_as_cancelled(stub_pipeline):
    svc = AnalysisService(workers=1, queue_size=8, batch_cfg=DUMMY_CFG)
    gate = threading.Event()
    real_attempt = svc._run_attempt

    def gated_attempt(job, attempt, resume=None):
        gate.wait(timeout=30)
        return real_attempt(job, attempt, resume=resume)

    svc._run_attempt = gated_attempt
    running = svc.submit("6006", tx_count=1, name="running")
    assert wait_for(lambda: svc.status(running)["state"] == "running")
    queued = [svc.submit("60%02x" % n, tx_count=1, name="q") for n in (7, 8)]
    # drain first with the runner still gated, so neither queued job can
    # sneak onto the worker before the drain
    svc.shutdown(wait=False)
    for job_id in queued:
        assert svc.status(job_id)["state"] == "cancelled"
    assert svc.stats()["jobs_cancelled"] == 2
    gate.set()
    svc.shutdown(wait=True, timeout=10)
    assert svc.status(running)["state"] == "done"


def test_shutdown_deadline_fails_wedged_job_exactly_once(stub_pipeline):
    svc = AnalysisService(workers=1, queue_size=8, batch_cfg=DUMMY_CFG)
    wedge = threading.Event()
    release = threading.Event()

    def wedged_attempt(job, attempt, resume=None):
        wedge.set()
        release.wait(timeout=60)
        return {"issues": [], "error": None, "report": None, "crashed": False}

    svc._run_attempt = wedged_attempt
    job = svc.submit("6009", tx_count=1, name="wedged")
    assert wedge.wait(10)
    t0 = time.time()
    svc.shutdown(wait=True, timeout=0.5)
    assert time.time() - t0 < 5.0        # the join deadline is shared
    status = svc.status(job)
    assert status["state"] == "failed"
    assert "shutdown" in status["error"]
    assert svc.stats()["jobs_failed"] == 1
    # the worker's own finalize loses the finish() race cleanly: counts
    # and terminal state are unchanged after it drains out
    release.set()
    assert wait_for(lambda: not svc._workers[0].is_alive(), 10)
    assert svc.status(job)["state"] == "failed"
    assert svc.stats()["jobs_failed"] == 1
    assert svc.stats()["jobs_done"] == 0


# -- job-context hygiene (satellite regression) ----------------------------


def test_crashed_job_context_never_leaks_to_next_job(stub_pipeline, service):
    """The deadline/cancel context a job installs on its worker thread
    must be cleared in the FINALLY path: a crashed job's context leaking
    onto the pool would drop the next job's async queries."""
    observed = []
    real_attempt = service._run_attempt

    def observing_attempt(job, attempt, resume=None):
        out = real_attempt(job, attempt, resume=resume)
        observed.append(solver_cache._job_context())
        return out

    service._run_attempt = observing_attempt
    faults.configure("scheduler_worker=crash:match=doomed")
    crash = service.submit("600a", tx_count=1, timeout=60, name="doomed")
    assert service.wait(crash, 20)
    assert service.status(crash)["state"] == "failed"
    faults.configure(None)
    ok = service.submit("600b", tx_count=1, timeout=60, name="clean")
    assert service.wait(ok, 20)
    # after every attempt — crashed or clean — the thread context is clear
    assert observed and all(
        ctx == (None, None) for ctx in observed
    ), observed


def test_quarantine_counts_attempts_not_submissions(stub_pipeline, service):
    """QUARANTINE_AFTER strikes are per crashed ATTEMPT: one submission
    of a deterministic crasher is enough to quarantine (attempt 0 + the
    retry), matching the documented semantics."""
    assert QUARANTINE_AFTER == 2
    faults.configure("scheduler_worker=crash")
    job = service.submit("600c", tx_count=1, name="crasher")
    assert service.wait(job, 20)
    assert service.status(job)["state"] == "failed"
    assert service.cache.is_quarantined(cache_key("", "600c"))
    reason = service.cache.quarantine_reason(cache_key("", "600c"))
    assert "crashed 2 times" in reason
