"""Real-pipeline fault matrix (the ISSUE acceptance property): with
faults armed at every seam, a multi-job service run completes every
non-poison job with the SAME issue sets as a clean run; a poison job
fails alone, with a structured report, and its code hash is
quarantined. These run real analyses on the CPU mesh (TEST_CFG-sized
batches) — scripts/check.sh deselects them by module name ('matrix');
the fast classification grid lives in test_faults.py."""

import pytest

import mythril_tpu.laser.tpu.backend as backend
from mythril_tpu.robustness import faults, retry
from mythril_tpu.service import AdmissionError, AnalysisService
from tests.service.test_multitenant import (
    ORIGIN_SRC,
    SUICIDE_SRC,
    TEST_CFG,
    contract_pair,
)


@pytest.fixture(autouse=True)
def small_batch(monkeypatch):
    monkeypatch.setattr(backend, "DEFAULT_BATCH_CFG", TEST_CFG)


def signature(result):
    """Order-insensitive issue signature for cross-run comparison."""
    return sorted(
        (i["swc-id"], i["contract"], i["title"], i["address"])
        for i in result["issues"]
    )


def run_service(spec, submissions, timeout=900):
    """One service run under ``spec``; returns {name: (status, result)}.
    Faults arm AFTER construction so service startup stays clean. The
    job budget is far above what these analyses need to drain, so the
    clean and faulted runs compare complete explorations, not whatever
    a loaded machine got through before a deadline."""
    service = AnalysisService(workers=2, batch_cfg=TEST_CFG, gather_window_s=0.5)
    faults.configure(spec)
    out = {}
    try:
        ids = {
            name: service.submit(r, c, tx_count=1, timeout=timeout, name=name)
            for name, (r, c) in submissions.items()
        }
        for name, job_id in ids.items():
            assert service.wait(job_id, timeout), name
            out[name] = (service.status(job_id), service.result(job_id))
        out["__stats__"] = service.stats()
    finally:
        faults.configure(None)
        service.shutdown(wait=True, timeout=30)
    return out


# every seam armed: an OOM round (ladder step 2), a transient round
# error (absorbed by ladder step 1), transfer faults in both directions
# (absorbed inside the round guard), a garbage device SAT dispatch, a
# probabilistic host-solve fault, one fallback-worker death, and one
# scheduler-attempt crash (absorbed by the retry-once path)
ALL_SEAMS_SPEC = (
    "seed=3;"
    "device_round=oom:n=1;"
    "device_round=error:n=1,after=1;"
    "transfer_up=error:n=1;"
    "transfer_down=error:n=1;"
    "solver_batch=garbage:n=1;"
    "host_solve=timeout:p=0.2;"
    "fallback_worker=worker_death:n=1;"
    "scheduler_worker=crash:n=1"
)


def test_service_run_with_faults_at_every_seam_matches_clean(
    generous_solver_budget,
):
    backend.warmup_device(TEST_CFG)
    submissions = {
        "suicidal": contract_pair(SUICIDE_SRC),
        "tx-origin": contract_pair(ORIGIN_SRC),
    }
    clean = run_service(None, submissions)
    assert clean["suicidal"][0]["state"] == "done"
    assert clean["tx-origin"][0]["state"] == "done"
    assert "106" in clean["suicidal"][1]["swc_ids"]
    assert "115" in clean["tx-origin"][1]["swc_ids"]
    assert not clean["suicidal"][1]["degraded"]
    assert clean["__stats__"]["degraded_rounds"] == 0
    assert clean["__stats__"]["device_retries"] == 0

    faulted = run_service(ALL_SEAMS_SPEC, submissions)
    for name in submissions:
        status, result = faulted[name]
        assert status["state"] == "done", (name, status)
        assert result["swc_ids"] == clean[name][1]["swc_ids"], name
        assert signature(result) == signature(clean[name][1]), name

    # the harness actually exercised the pipeline: the scheduler seam is
    # crossed once per attempt, so at LEAST that rule fired, and the
    # absorbed crash surfaces as a retried/degraded job
    stats = faulted["__stats__"]
    assert stats["jobs_retried"] >= 1
    assert stats["jobs_failed"] == 0
    assert any(
        faulted[name][0]["retried"] and faulted[name][0]["degraded"]
        for name in submissions
    )
    # absorbed faults never count as breaker trips at these rates
    assert stats["breaker_state"] == "closed"


def test_poison_job_quarantined_others_unaffected():
    backend.warmup_device(TEST_CFG)
    r_poison, c_poison = contract_pair(SUICIDE_SRC)
    r_ok, c_ok = contract_pair(ORIGIN_SRC)

    service = AnalysisService(workers=2, batch_cfg=TEST_CFG, gather_window_s=0.5)
    faults.configure("scheduler_worker=crash:match=poison")
    try:
        poison = service.submit(
            r_poison, c_poison, tx_count=1, timeout=120, name="poison-pill"
        )
        ok = service.submit(r_ok, c_ok, tx_count=1, timeout=120, name="benign")
        assert service.wait(poison, 300) and service.wait(ok, 300)

        status = service.status(poison)
        assert status["state"] == "failed"
        assert status["error_report"]["exception"] == "InjectedCrash"
        assert status["error_report"]["seam"] == "scheduler_worker"
        assert status["retried"]  # the one retry was spent before failing

        # the benign co-tenant is untouched
        ok_status, ok_result = service.status(ok), service.result(ok)
        assert ok_status["state"] == "done"
        assert "115" in ok_result["swc_ids"]
        assert all(i["contract"] == "benign" for i in ok_result["issues"])

        # the poison hash is now rejected at admission
        with pytest.raises(AdmissionError, match="quarantined"):
            service.submit(
                r_poison, c_poison, tx_count=1, timeout=120, name="poison-pill"
            )
        assert service.stats()["quarantined_jobs"] == 1
    finally:
        faults.configure(None)
        service.shutdown(wait=True, timeout=30)


def test_breaker_opens_under_persistent_device_failure_jobs_complete(
    generous_solver_budget,
):
    """Ladder step 3 end-to-end: every device round fails, the breaker
    opens, and the jobs still complete HOST-ONLY with the clean issue
    sets and degraded=true."""
    backend.warmup_device(TEST_CFG)
    submissions = {
        "suicidal": contract_pair(SUICIDE_SRC),
        "tx-origin": contract_pair(ORIGIN_SRC),
    }
    clean = run_service(None, submissions)
    faulted = run_service("device_round=error", submissions)
    for name in submissions:
        status, result = faulted[name]
        assert status["state"] == "done", (name, status)
        assert result["swc_ids"] == clean[name][1]["swc_ids"], name
        assert signature(result) == signature(clean[name][1]), name
    stats = faulted["__stats__"]
    assert stats["degraded_rounds"] >= 1
    # either rounds kept degrading below the trip threshold or the
    # breaker opened; both are legitimate host-only completions, but
    # persistent failure at every crossing must never FAIL a job
    assert stats["jobs_failed"] == 0
    assert retry.BREAKER.trips == stats["breaker_trips"]
