"""Job scheduler for the multi-tenant analysis service.

``AnalysisService`` owns the whole service runtime: admission control
over submitted jobs, a bounded queue with backpressure, a small pool of
worker threads, per-job deadlines and cancellation, the shared-lane
coordinator (lanes.py) and the result cache (cache.py).

Job lifecycle (docs/SERVICE.md):

    submit() -> QUEUED -> RUNNING -> DONE | FAILED | CANCELLED

  * submit() rejects malformed input (AdmissionError) and applies
    backpressure when the queue is full (QueueFullError) — callers
    retry or shed load; the service never buffers unboundedly
  * a cache hit at submission completes the job as DONE immediately
    (cache_hit=True) without ever entering the queue
  * cancel() flips the job's cancel event: a QUEUED job completes as
    CANCELLED without running; a RUNNING job is stopped at the next
    host-loop / batch-loop check with its in-flight states put back
    (laser/tpu/backend.py, laser/evm/svm.py)

Concurrency model: every worker runs ONE job's full analysis pipeline
(SymExecWrapper -> detection harvest) under the service-wide HOST lock.
The lock is released only while the job waits in / runs a shared device
round (lanes.py invariant I3) — that window is what lets several jobs'
host phases interleave and their frontiers share one device batch. All
the process-global singletons the pipeline touches (incremental solver
core, detection-module issue lists, the keccak function manager) are
therefore never entered concurrently (invariant I2).

Jobs execute under a unique internal contract name (``<name>#<id>``) so
the singleton detection modules' findings and dedup caches split
exactly per job at harvest (analysis/security.py
harvest_callback_issues); the user-facing name is restored on the
reported issues afterwards, which keeps repeated submissions
byte-identical with their cached reports.
"""

import itertools
import logging
import threading
import time
from collections import deque
from enum import Enum
from typing import Dict, List, Optional

from mythril_tpu import obs
from mythril_tpu.exceptions import DeviceWarmupError
from mythril_tpu.obs import catalog as _obs_catalog
from mythril_tpu.robustness import faults
from mythril_tpu.robustness.checkpoint import CheckpointJournal
from mythril_tpu.service.cache import QUARANTINE_AFTER, ResultCache, cache_key
from mythril_tpu.service.lanes import (
    DEFAULT_GATHER_WINDOW_S,
    JobContext,
    LaneCoordinator,
)
from mythril_tpu.support import events

log = logging.getLogger(__name__)

# analysis contract address, same placeholder the CLI bytecode path uses
JOB_ADDRESS = 0x1234

# hard ceiling on submitted code (creation + runtime): far above EIP-170
# but low enough that a malformed submission cannot balloon the packer
MAX_CODE_BYTES = 1 << 20

# shared by every AnalysisService in the process — see _ids in __init__
_JOB_IDS = itertools.count(1)


class AdmissionError(ValueError):
    """The submission is malformed and will never be accepted."""


class QueueFullError(RuntimeError):
    """Backpressure: the job queue is at capacity; retry later."""


class JobState(Enum):
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    FAILED = "failed"
    CANCELLED = "cancelled"


class AnalysisJob:
    """One submitted analysis: code + parameters + lifecycle state."""

    def __init__(
        self,
        job_id: int,
        name: str,
        runtime_hex: str,
        creation_hex: str,
        tx_count: int,
        timeout: Optional[float],
        modules: Optional[List[str]],
        max_depth: int,
    ):
        self.id = job_id
        self.name = name
        self.runtime_hex = runtime_hex
        self.creation_hex = creation_hex
        self.tx_count = tx_count
        self.timeout = timeout
        self.modules = modules
        self.max_depth = max_depth
        self.key = cache_key(creation_hex, runtime_hex)
        self.state = JobState.QUEUED
        self.submitted_at = time.time()
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.wall_s: Optional[float] = None
        self.cache_hit = False
        self.result: Optional[Dict] = None
        self.error: Optional[str] = None
        # structured crash classification (exception class, seam, round
        # number, attempt) for FAILED jobs — the quarantine cites it
        self.error_report: Optional[Dict] = None
        # robustness ladder attribution, summed across attempts
        self.degraded = False
        self.retried = False
        self.device_retries = 0
        self.degraded_rounds = 0
        # per-job span timeline (api submit with trace=True): the
        # tracer cursor at attempt start bounds this job's event slice
        self.trace = False
        self.trace_cursor = 0
        self.cancel_event = threading.Event()
        self.done_event = threading.Event()
        self._finish_lock = threading.Lock()
        # streamed partial results (`watch` op): issue events appended
        # live by the service's bus listener as detection modules fire.
        # Append-only; watchers iterate by index (never mutated in
        # place), so readers need no lock — _stream_cv just wakes them.
        self.stream_events: List[Dict] = []
        self._stream_cv = threading.Condition(threading.Lock())

    def push_stream_event(self, event: Dict) -> None:
        with self._stream_cv:
            self.stream_events.append(event)
            self._stream_cv.notify_all()

    @property
    def internal_name(self) -> str:
        """Contract name the job executes under — unique per job so the
        singleton detection modules' state splits exactly at harvest."""
        return "%s#%d" % (self.name, self.id)

    def finish(self, state: JobState) -> bool:
        """Terminal transition; idempotent. Returns True only for the
        ONE caller that actually finished the job — shutdown marking a
        wedged job FAILED can race its worker's own finalize, and
        exactly one of them may update the service counters."""
        with self._finish_lock:
            if self.done_event.is_set():
                return False
            self.state = state
            self.finished_at = time.time()
            if self.started_at is not None:
                self.wall_s = self.finished_at - self.started_at
            self.done_event.set()
            return True

    def status_dict(self) -> Dict:
        return {
            "job_id": self.id,
            "name": self.name,
            "state": self.state.value,
            "cache_hit": self.cache_hit,
            "wall_s": self.wall_s,
            "error": self.error,
            "error_report": self.error_report,
            "degraded": self.degraded,
            "retried": self.retried,
            "device_retries": self.device_retries,
            "degraded_rounds": self.degraded_rounds,
        }


def _clean_hex(value: Optional[str], what: str) -> str:
    value = (value or "").strip()
    if value.startswith(("0x", "0X")):
        value = value[2:]
    if len(value) % 2 != 0:
        raise AdmissionError("%s: odd-length hex" % what)
    try:
        bytes.fromhex(value)
    except ValueError:
        raise AdmissionError("%s: invalid hex" % what)
    return value


class AnalysisService:
    """The persistent in-process analysis service."""

    def __init__(
        self,
        workers: int = 2,
        queue_size: int = 16,
        batch_cfg=None,
        gather_window_s: float = DEFAULT_GATHER_WINDOW_S,
        cache_entries: int = 256,
        warm: bool = False,
        cache: Optional[ResultCache] = None,
    ):
        if batch_cfg is None:
            from mythril_tpu.laser.tpu import backend

            batch_cfg = backend.DEFAULT_BATCH_CFG
        self.batch_cfg = batch_cfg
        # ONE lock serializes every job's host-phase Python (invariant
        # I2); acquired exactly once per scope so the coordinator can
        # release it while a job parks in a device round (I3)
        self.host_lock = threading.RLock()
        self.coordinator = LaneCoordinator(
            batch_cfg, self.host_lock, gather_window_s=gather_window_s
        )
        # injectable cache backend: the fleet tier passes a
        # fleet/store.DurableResultCache so results, solver memos and
        # quarantine strikes survive restarts and are shared
        # cross-process; default stays the in-memory LRU
        self.cache = cache if cache is not None else ResultCache(
            max_entries=cache_entries
        )
        # frontier checkpoints (keyed by job id): a FAILED job's one
        # retry resumes from its latest journaled frontier
        self.journal = CheckpointJournal()
        self.queue_size = queue_size
        self._queue: "deque[AnalysisJob]" = deque()
        self._queue_cv = threading.Condition(threading.Lock())
        self._jobs: Dict[int, AnalysisJob] = {}
        # PROCESS-global, not per-service: job ids feed internal_name,
        # which the issue-bus listener uses to attribute stream events —
        # two service instances in one process (fleet in-proc tests)
        # must never mint colliding "<name>#<id>" identities. 0 marks a
        # free lane (batch.py).
        self._ids = _JOB_IDS
        self._shutdown = False
        # service counters: every mutation goes through _count() (or
        # happens while already holding _queue_cv's lock) so concurrent
        # worker finishes cannot lose increments (ISSUE 9 satellite);
        # stats() reads them under the same lock
        self.jobs_submitted = 0
        self.jobs_done = 0
        self.jobs_failed = 0
        self.jobs_cancelled = 0
        self.jobs_retried = 0
        # Prometheus exposition: this instance's samples replace any
        # prior service's in the shared registry (keyed slot)
        _obs_catalog.register_service(self)
        # streaming partial results: detection modules publish every
        # finding on the process-wide issue bus the moment it exists;
        # the listener maps it back to the owning job via the unique
        # internal contract name and appends a `watch` stream event
        self._issue_listener = events.ISSUE_BUS.subscribe(self._on_issue)
        self._workers = [
            threading.Thread(
                target=self._worker, name="analysis-worker-%d" % i, daemon=True
            )
            for i in range(max(1, workers))
        ]
        for thread in self._workers:
            thread.start()
        if warm:
            # compile the shared device kernels up front so the first
            # job does not serialize every tenant behind the XLA compile
            from mythril_tpu.laser.tpu import backend

            backend.warmup_device(batch_cfg)

    # ------------------------------------------------------------- frontend

    def submit(
        self,
        runtime_hex: str,
        creation_hex: Optional[str] = None,
        tx_count: int = 2,
        timeout: Optional[float] = 60,
        modules: Optional[List[str]] = None,
        name: str = "contract",
        max_depth: int = 128,
        trace: bool = False,
    ) -> int:
        """Admit a job; returns its id. Raises AdmissionError on
        malformed input, QueueFullError under backpressure."""
        if self._shutdown:
            raise RuntimeError("service is shut down")
        runtime_hex = _clean_hex(runtime_hex, "runtime code")
        creation_hex = _clean_hex(creation_hex, "creation code")
        if not runtime_hex and not creation_hex:
            raise AdmissionError("empty submission: no code to analyze")
        if (len(runtime_hex) + len(creation_hex)) // 2 > MAX_CODE_BYTES:
            raise AdmissionError("submitted code exceeds %d bytes" % MAX_CODE_BYTES)
        if tx_count < 1:
            raise AdmissionError("tx_count must be >= 1")
        if timeout is not None and timeout <= 0:
            raise AdmissionError("timeout must be positive")
        reason = self.cache.quarantine_reason(
            cache_key(creation_hex, runtime_hex)
        )
        if reason is not None:
            raise AdmissionError("code hash is quarantined: %s" % reason)

        job = AnalysisJob(
            next(self._ids), name, runtime_hex, creation_hex,
            tx_count, timeout, modules, max_depth,
        )
        if trace:
            job.trace = True
            obs.TRACER.enable()
        self._jobs[job.id] = job
        self._count("jobs_submitted")

        entry = self.cache.get(job.key, tx_count, modules, timeout)
        if entry is not None:
            job.started_at = time.time()
            job.cache_hit = True
            job.result = {
                "issues": entry.issues,
                "swc_ids": entry.swc_ids,
                "cache_hit": True,
                "cold_wall_s": entry.cold_wall_s,
            }
            # a watcher of a warm job still gets the full issue stream
            # (source-tagged): the cached findings never re-fire on the
            # bus, so replay them as stream events here
            now = time.time()
            for issue_dict in entry.issues:
                issue_dict = dict(issue_dict)
                issue_dict["contract"] = job.name
                job.push_stream_event(
                    {
                        "event": "issue",
                        "job_id": job.id,
                        "issue": issue_dict,
                        "source": "cache",
                        "t": now,
                    }
                )
            job.finish(JobState.DONE)
            self._count("jobs_done")
            return job.id

        with self._queue_cv:
            if len(self._queue) >= self.queue_size:
                del self._jobs[job.id]
                self.jobs_submitted -= 1
                raise QueueFullError(
                    "queue full (%d jobs); retry later" % self.queue_size
                )
            self._queue.append(job)
            self._queue_cv.notify()
        return job.id

    def status(self, job_id: int) -> Dict:
        return self._job(job_id).status_dict()

    def result(self, job_id: int, wait: bool = False,
               timeout: Optional[float] = None) -> Optional[Dict]:
        job = self._job(job_id)
        if wait:
            job.done_event.wait(timeout)
        return job.result

    def wait(self, job_id: int, timeout: Optional[float] = None) -> bool:
        return self._job(job_id).done_event.wait(timeout)

    # ---------------------------------------------- streaming (`watch` op)

    def _on_issue(self, contract_name: str, issue) -> None:
        """Issue-bus listener: attribute a freshly fired finding to the
        owning job (unique internal name ``<name>#<id>``) and append a
        stream event. Findings from other services' jobs — or from the
        plain CLI path, which never runs under an internal name — fall
        through silently."""
        _, sep, id_part = str(contract_name).rpartition("#")
        if not sep or not id_part.isdigit():
            return
        job = self._jobs.get(int(id_part))
        if job is None or job.internal_name != contract_name:
            return
        try:
            issue_dict = dict(issue.as_dict)
        except Exception as e:  # pragma: no cover - defensive
            issue_dict = {"title": str(issue), "render_error": str(e)}
        # the watcher asked about <name>, not the internal tenancy name
        issue_dict["contract"] = job.name
        job.push_stream_event(
            {
                "event": "issue",
                "job_id": job.id,
                "issue": issue_dict,
                "t": time.time(),
            }
        )

    def watch(self, job_id: int, poll_s: float = 0.1):
        """Generator of stream events for one job: every ``issue`` event
        as detection modules fire (replayed from the start for a late
        subscriber), terminated by exactly one ``end`` event carrying
        the final state. Safe to call on an already-finished job — the
        full history replays, then ``end``."""
        job = self._job(job_id)
        idx = 0
        while True:
            events_now = job.stream_events
            while idx < len(events_now):
                yield events_now[idx]
                idx += 1
            if job.done_event.is_set():
                # drain anything that raced in between the len() read
                # and the done check, then finish
                events_now = job.stream_events
                while idx < len(events_now):
                    yield events_now[idx]
                    idx += 1
                break
            with job._stream_cv:
                if len(job.stream_events) == idx and not job.done_event.is_set():
                    job._stream_cv.wait(poll_s)
        status = job.status_dict()
        result = job.result or {}
        yield {
            "event": "end",
            "job_id": job.id,
            "state": status["state"],
            "cache_hit": status["cache_hit"],
            "wall_s": status["wall_s"],
            "error": status["error"],
            "issues": len(result.get("issues", [])),
            "swc_ids": result.get("swc_ids", []),
            "t": time.time(),
        }

    def cancel(self, job_id: int) -> bool:
        """Request cancellation; returns True if the job had not already
        finished. Queued jobs complete as CANCELLED without running;
        running jobs stop at the engine's next cancellation check with
        their in-flight states put back (never dropped)."""
        job = self._job(job_id)
        if job.done_event.is_set():
            return False
        job.cancel_event.set()
        with self._queue_cv:
            self._queue_cv.notify_all()
        return True

    def stats(self) -> Dict:
        from mythril_tpu.robustness import retry

        ckpt = self.journal.stats()
        with self._queue_cv:
            counters = {
                "jobs_submitted": self.jobs_submitted,
                "jobs_done": self.jobs_done,
                "jobs_failed": self.jobs_failed,
                "jobs_cancelled": self.jobs_cancelled,
                "jobs_retried": self.jobs_retried,
                "queued": len(self._queue),
                # capacity rides along so fleet admission control can
                # compute queue pressure without configuration coupling
                "queue_size": self.queue_size,
            }
        return {
            **counters,
            "rounds": self.coordinator.rounds,
            "shared_rounds": self.coordinator.shared_rounds,
            "max_resident_jobs": self.coordinator.max_resident_jobs,
            "device_retries": self.coordinator.device_retries,
            "degraded_rounds": self.coordinator.degraded_rounds,
            "breaker_state": retry.BREAKER.state(),
            "breaker_trips": retry.BREAKER.trips,
            "checkpoint_overhead_s": ckpt["overhead_s"],
            "checkpoints": ckpt["snapshots"],
            "quarantined_jobs": self.cache.stats()["quarantined"],
            "cache": self.cache.stats(),
        }

    def shutdown(self, wait: bool = True, timeout: Optional[float] = 30) -> None:
        """Stop the service: still-queued jobs complete as CANCELLED
        immediately; workers are joined against ONE shared deadline (a
        wedged job cannot hang shutdown); any job still RUNNING when the
        deadline expires is finished FAILED with a "shutdown" reason
        (its worker's own later finalize is a no-op: finish() is
        idempotent and returns False to the loser)."""
        self._shutdown = True
        events.ISSUE_BUS.unsubscribe(self._issue_listener)
        with self._queue_cv:
            drained = list(self._queue)
            self._queue.clear()
            self._queue_cv.notify_all()
        for job in drained:
            if job.finish(JobState.CANCELLED):
                self._count("jobs_cancelled")
        if not wait:
            return
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        for thread in self._workers:
            if deadline is None:
                thread.join()
            else:
                thread.join(max(0.0, deadline - time.monotonic()))
        for job in list(self._jobs.values()):
            if not job.done_event.is_set():
                # ask the engine to stop at its next cancellation check
                # (in-flight states put back per the timeout-path
                # semantics), but do not wait for it: the job fails NOW
                job.cancel_event.set()
                job.error = "service shutdown before job completed"
                if job.finish(JobState.FAILED):
                    self._count("jobs_failed")

    # -------------------------------------------------------------- workers

    def _job(self, job_id: int) -> AnalysisJob:
        job = self._jobs.get(job_id)
        if job is None:
            raise KeyError("unknown job id %r" % job_id)
        return job

    def _count(self, counter: str, delta: int = 1) -> None:
        """Adjust a jobs_* counter under the scheduler lock. A bare
        ``+= 1`` is a read-modify-write: two workers finalizing
        concurrently can lose one. Callers that already hold
        ``_queue_cv`` mutate directly instead (the Condition lock is
        not reentrant)."""
        with self._queue_cv:
            setattr(self, counter, getattr(self, counter) + delta)

    def _next_job(self) -> Optional[AnalysisJob]:
        with self._queue_cv:
            while True:
                while self._queue:
                    job = self._queue.popleft()
                    if job.cancel_event.is_set():
                        if job.finish(JobState.CANCELLED):
                            self.jobs_cancelled += 1
                        continue
                    return job
                if self._shutdown:
                    return None
                self._queue_cv.wait(timeout=0.2)

    def _worker(self) -> None:
        while True:
            job = self._next_job()
            if job is None:
                return
            try:
                self._run_job(job)
            except BaseException as e:  # pragma: no cover - worker survives
                # last-ditch isolation: _run_job classifies crashes
                # itself, so reaching here means the SCHEDULER plumbing
                # failed — the job dies, the worker survives
                log.exception("worker crashed on job %d: %s", job.id, e)
                job.error = "internal worker failure: %s" % e
                if job.finish(JobState.FAILED):
                    self._count("jobs_failed")

    def _run_job(self, job: AnalysisJob) -> None:
        """One job, at most two attempts.

        A crashed first attempt records a strike against the code hash
        and retries ONCE — from the job's latest frontier checkpoint
        when one was journaled, from scratch otherwise. A second crash
        records the second strike (= quarantine: later submissions of
        this hash are rejected at admission) and the job fails with its
        structured error report. Transient faults the retry absorbed
        leave no strikes behind (_finalize -> cache.record_success)."""
        job.state = JobState.RUNNING
        job.started_at = time.time()
        job.trace_cursor = obs.TRACER.cursor()
        outcome = self._run_attempt(job, attempt=0)
        if (
            outcome["crashed"]
            and not job.cancel_event.is_set()
            and not self._shutdown
        ):
            strikes = self.cache.record_crash(job.key, outcome["report"])
            if strikes >= QUARANTINE_AFTER:
                obs.TRACER.mark(
                    "quarantine", pid=job.id, job=job.name, strikes=strikes,
                )
            if strikes < QUARANTINE_AFTER:
                ckpt = self.journal.latest(job.id)
                log.warning(
                    "retrying job %d once from %s",
                    job.id,
                    ckpt if ckpt is not None else "scratch",
                )
                job.retried = True
                self._count("jobs_retried")
                outcome = self._run_attempt(job, attempt=1, resume=ckpt)
                if outcome["crashed"] and not job.cancel_event.is_set():
                    strikes = self.cache.record_crash(
                        job.key, outcome["report"]
                    )
                    if strikes >= QUARANTINE_AFTER:
                        obs.TRACER.mark(
                            "quarantine", pid=job.id, job=job.name,
                            strikes=strikes,
                        )
        self.journal.clear(job.id)
        self._finalize(job, outcome)

    def _run_attempt(
        self, job: AnalysisJob, attempt: int, resume=None
    ) -> Dict:
        """One analysis attempt; never raises. Returns
        ``{"issues", "error", "report", "crashed"}`` and accumulates the
        attempt's ladder counters onto the job."""
        from mythril_tpu.analysis.security import fire_lasers_for_job
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.ethereum.evmcontract import EVMContract

        ctx = JobContext(job.id, self.coordinator, job.cancel_event)
        self.coordinator.job_started()
        outcome: Dict = {
            "issues": [], "error": None, "report": None, "crashed": False,
        }
        # solver-seam warmth + fallback hygiene (laser/tpu/solver_cache):
        # seed the verdict memo accumulated by earlier runs of this code
        # hash, and tag this thread's async host-solver submissions with
        # the job's deadline and cancel event so a cancelled or expired
        # job's pending queries are DROPPED by the pool, never solved.
        from mythril_tpu.laser.tpu import solver_cache

        laser_box: List = []
        rounds_offset = resume.rounds_done if resume is not None else 0

        def pre_exec(laser):
            ctx.install(laser)
            laser_box.append(laser)
            self.journal.install(
                job.id, laser, total_rounds=job.tx_count,
                rounds_offset=rounds_offset,
            )

        try:
            solver_cache.GLOBAL.seed_memo(self.cache.get_solver_memo(job.key))
            solver_cache.set_job_context(
                deadline=(
                    job.started_at + float(job.timeout)
                    if job.timeout else None
                ),
                cancel_event=job.cancel_event,
            )
            faults.fire(faults.SCHEDULER_WORKER, context=job.name)
            contract = EVMContract(
                code=job.runtime_hex,
                creation_code=job.creation_hex,
                name=job.internal_name,
            )
            with self.host_lock:
                sym = SymExecWrapper(
                    contract,
                    address=JOB_ADDRESS,
                    strategy="tpu-batch",
                    execution_timeout=(
                        int(job.timeout) if job.timeout else None
                    ),
                    transaction_count=job.tx_count,
                    max_depth=job.max_depth,
                    modules=job.modules,
                    pre_exec_hook=pre_exec,
                    fresh_solver_core=False,
                    resume_from=resume,
                )
                outcome["issues"] = fire_lasers_for_job(
                    sym, {job.internal_name}, job.modules
                )
        except Exception as e:
            rounds = 0
            if laser_box:
                rounds = getattr(
                    laser_box[0], "executed_transaction_rounds", 0
                )
            outcome["error"] = str(e)
            # a device that failed to warm up is no fault of the
            # contract: fail the job without a retry or a strike
            outcome["crashed"] = not isinstance(e, DeviceWarmupError)
            outcome["report"] = {
                "exception": type(e).__name__,
                "seam": getattr(e, "seam", None),
                "kind": getattr(e, "kind", None),
                "round": rounds,
                "attempt": attempt,
                "message": str(e),
            }
            log.warning(
                "job %d attempt %d crashed (%s at seam %s, round %d)",
                job.id, attempt, type(e).__name__,
                getattr(e, "seam", None) or "-", rounds,
            )
        finally:
            # ALWAYS clear this worker thread's job context: a crashed
            # job's deadline/cancel context must never leak onto the
            # next job this worker picks up (satellite regression)
            solver_cache.clear_job_context()
            self.coordinator.job_finished()
            if laser_box:
                from mythril_tpu.laser.tpu import backend

                strat = backend.find_tpu_strategy(laser_box[0].strategy)
                if strat is not None:
                    job.device_retries += strat.device_retries
                    job.degraded_rounds += strat.degraded_rounds
        return outcome

    def _finalize(self, job: AnalysisJob, outcome: Dict) -> None:
        from mythril_tpu.laser.tpu import solver_cache

        job.degraded = bool(
            job.retried or job.device_retries or job.degraded_rounds
        )
        if job.cancel_event.is_set():
            if job.finish(JobState.CANCELLED):
                self._count("jobs_cancelled")
            return
        if outcome["error"] is not None:
            job.error = outcome["error"]
            job.error_report = outcome["report"]
            if job.finish(JobState.FAILED):
                self._count("jobs_failed")
            return

        self.cache.record_success(job.key)
        issues = outcome["issues"]
        # the user asked about <name>, not the internal tenancy name
        for issue in issues:
            issue.contract = job.name
        issue_dicts = [issue.as_dict for issue in issues]
        swc_ids = sorted({issue.swc_id for issue in issues})
        job.result = {
            "issues": issue_dicts,
            "swc_ids": swc_ids,
            "cache_hit": False,
            "degraded": job.degraded,
            "retried": job.retried,
            "device_retries": job.device_retries,
            "degraded_rounds": job.degraded_rounds,
        }
        if job.trace and obs.TRACER.enabled:
            # per-job span timeline: this job's process row (its own
            # pid) plus the shared device/solver rows (pid 0) since the
            # attempt started
            job.result["trace_events"] = obs.TRACER.chrome_events(
                since=job.trace_cursor, pids={0, job.id}
            )
        if not job.finish(JobState.DONE):
            # shutdown failed this job while its worker was finalizing;
            # the shutdown verdict stands and nothing is cached
            return
        self._count("jobs_done")
        # export the verdicts this job decided so resubmissions of the
        # same contract (any parameters) start with a warm memo table
        self.cache.put_solver_memo(job.key, solver_cache.GLOBAL.export_memo())
        self.cache.put(
            job.key,
            job.tx_count,
            job.modules,
            job.timeout,
            issue_dicts,
            swc_ids,
            cold_wall_s=job.wall_s or 0.0,
            static_tables=self._static_tables(job),
        )

    @staticmethod
    def _static_tables(job: AnalysisJob) -> list:
        """(code, tables) pairs for the entry's artifact side; analyze()
        is memoized so this only reads the pass's own cache."""
        from mythril_tpu.analysis import static_pass

        tables = []
        for code_hex in (job.runtime_hex, job.creation_hex):
            if code_hex:
                code = bytes.fromhex(code_hex)
                try:
                    tables.append((code, static_pass.analyze(code)))
                except Exception:  # noqa: artifact side is best-effort
                    pass
        return tables
