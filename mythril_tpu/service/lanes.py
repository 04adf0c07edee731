"""Shared-lane allocation: one device round, many jobs.

Single-tenant ``exec_batch`` (laser/tpu/backend.py) gives the whole lane
axis to one analysis; after frontier collapse most lanes ride along
dead. The coordinator here multiplexes the device-bound frontiers of
several in-flight jobs into ONE ``StateBatch`` round instead:

  * every job thread that reaches phase B parks its staged states in a
    round request; the first arriver leads the round
  * the leader waits a short gather window for the other active jobs to
    reach their own phase B, then packs ALL gathered requests into one
    shared ``DeviceBridge`` — each lane stamped with the owning job in
    the ``job_id`` plane (laser/tpu/batch.py)
  * one ``backend._run_device`` round advances everyone's lanes in
    lockstep; device forking copies the parent's ``job_id`` through the
    generic plane gather, so ownership is exact for device-born states
  * at harvest every participant splits the downloaded batch on its own
    ``job_id`` — lanes, step counts, ``static_pruned`` and coverage all
    attribute to the job that owns them

Lane-sharing invariants (docs/SERVICE.md):

  I1  a lane's job_id is written exactly once (at pack) and only copied
      thereafter (fork gather); 0 means single-tenant / never packed
  I2  host-side Python (packing, unpacking, hook replay, solving) runs
      under the service's HOST lock — the global singletons the analysis
      pipeline leans on (incremental solver core, detection-module issue
      lists, keccak manager) are never entered concurrently
  I3  the HOST lock is RELEASED while a job waits in / runs the shared
      device round, which is exactly what lets a second job run its
      host phase and join the same round
  I4  a cancelled job's pending request is returned unpacked (result
      None) — its states go back to the job's work list, never dropped

The merged round runs under the UNION of the participants' host-op sets
(a lane may freeze-trap earlier than its own job strictly requires —
sound: the host path resumes it with full fidelity), the AND of their
``prune_revert`` flags, and the MIN of their deadlines.
"""

import logging
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from mythril_tpu import obs
from mythril_tpu.obs import catalog as _cat

log = logging.getLogger(__name__)

# how long the round leader waits for other active jobs to reach their
# device phase before running with whoever showed up
DEFAULT_GATHER_WINDOW_S = 0.25


class JobContext:
    """Per-job handle installed on the LaserEVM (``laser.job_ctx``) via
    SymExecWrapper's pre_exec_hook; exec_batch picks it up to route
    device rounds through the coordinator and to poll cancellation."""

    def __init__(self, job_id: int, coordinator: "LaneCoordinator", cancel_event):
        if job_id < 1:
            raise ValueError("job ids start at 1 (0 marks a free lane)")
        self.job_id = job_id
        self.coordinator = coordinator
        self.cancel_event = cancel_event

    def cancelled(self) -> bool:
        return self.cancel_event is not None and self.cancel_event.is_set()

    def install(self, laser) -> None:
        laser.job_ctx = self


class RoundResult:
    """What one participant gets back from a shared round."""

    def __init__(self, out, bridge, packed, failed, device_wall: float,
                 degraded: bool = False, retries: int = 0, oom: bool = False):
        # host-side StateBatch of the WHOLE merged round; callers mask
        # their lanes with ``out.job_id == their job id``
        self.out = out
        self.bridge = bridge
        self.packed = packed  # states that made it into a lane
        self.failed = failed  # states that did not (PackError / overflow)
        self.device_wall = device_wall
        # robustness ladder attribution (every participant of a round
        # shares these: each experienced the retry delay / the degrade)
        self.degraded = degraded
        self.retries = retries
        self.oom = oom


class _RoundRequest:
    def __init__(self, job_id, states, host_ops, tape_replayers,
                 value_replayers, prune_revert, deadline, cancel_event):
        self.job_id = job_id
        self.states = states
        self.host_ops = host_ops
        self.tape_replayers = tape_replayers
        self.value_replayers = value_replayers
        self.prune_revert = prune_revert
        self.deadline = deadline
        self.cancel_event = cancel_event
        self.packed: list = []
        self.failed: list = []
        self.result: Optional[RoundResult] = None
        self.error: Optional[BaseException] = None
        self.done = False

    def cancelled(self) -> bool:
        return self.cancel_event is not None and self.cancel_event.is_set()


class LaneCoordinator:
    """Gathers concurrent jobs' device-bound frontiers into shared rounds.

    ``host_lock`` is the service-wide lock serializing all host-phase
    Python; callers enter run_round() HOLDING it (acquired exactly once)
    and get it back on return — it is released only while parked here.
    """

    def __init__(self, cfg, host_lock, gather_window_s: float = DEFAULT_GATHER_WINDOW_S):
        self.cfg = cfg
        self.host_lock = host_lock
        self.gather_window_s = gather_window_s
        self._cv = threading.Condition(threading.Lock())
        self._pending: List[_RoundRequest] = []
        self._leader: Optional[_RoundRequest] = None
        self._active_jobs = 0
        # high-water mark of DISTINCT jobs resident in one device batch,
        # measured on the job_id plane after the round — the acceptance
        # witness that lanes are actually shared
        self.max_resident_jobs = 0
        self.rounds = 0
        self.shared_rounds = 0
        # service-wide robustness ladder aggregates (bench fields)
        self.device_retries = 0
        self.degraded_rounds = 0
        # per-job storage-ring drain counts for the current bridge epoch
        self.ss_drains_by_job: Dict[int, int] = {}

    # ---------------------------------------------------------- job census

    def job_started(self) -> None:
        with self._cv:
            self._active_jobs += 1

    def job_finished(self) -> None:
        with self._cv:
            self._active_jobs = max(0, self._active_jobs - 1)
            # a job that exits mid-gather must not leave the leader
            # waiting for it
            self._cv.notify_all()

    def active_jobs(self) -> int:
        with self._cv:
            return max(1, self._active_jobs)

    # -------------------------------------------------------------- rounds

    def run_round(
        self,
        *,
        job_id: int,
        states,
        host_ops,
        tape_replayers,
        value_replayers,
        prune_revert: bool,
        deadline: Optional[float],
        cancel_event=None,
    ) -> Optional[RoundResult]:
        """Park this job's staged frontier in the next shared round.

        Returns the RoundResult, or None if the job was cancelled before
        its states reached the device (invariant I4: the caller must put
        ``states`` back on its work list). Called with the host lock
        held; the lock is released while waiting/running and re-held on
        return."""
        req = _RoundRequest(
            job_id, states, host_ops, tape_replayers, value_replayers,
            prune_revert, deadline, cancel_event,
        )
        with self._cv:
            self._pending.append(req)
            self._cv.notify_all()
        self.host_lock.release()
        try:
            while True:
                with self._cv:
                    while not req.done and self._leader is not None:
                        self._cv.wait(timeout=0.05)
                    if req.done:
                        break
                    self._leader = req
                try:
                    self._lead_round()
                finally:
                    with self._cv:
                        self._leader = None
                        self._cv.notify_all()
        finally:
            self.host_lock.acquire()
        if req.error is not None:
            raise req.error
        return req.result

    def _gather(self, leader: _RoundRequest) -> List[_RoundRequest]:
        """Wait out the gather window, then take every pending request
        (cancelled ones are completed with result None on the spot)."""
        deadline = time.monotonic() + self.gather_window_s
        with self._cv:
            while True:
                live = [r for r in self._pending if not r.cancelled()]
                # every active job already waiting -> no point holding
                # the round open any longer
                if len(live) >= max(1, self._active_jobs):
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=min(remaining, 0.02))
            batch: List[_RoundRequest] = []
            for r in self._pending:
                if r.cancelled():
                    r.result = None
                    r.done = True
                else:
                    batch.append(r)
            self._pending = []
            self._cv.notify_all()
        if leader not in batch and not leader.done:
            # the leader itself was cancelled mid-gather; it still leads
            # the round for the others (its own result stays None)
            pass
        return batch

    def _lead_round(self) -> None:
        from mythril_tpu.laser.tpu.bridge import DeviceBridge, PackError
        from mythril_tpu.robustness import retry

        leader = self._leader
        batch = self._gather(leader)
        if not batch:
            return
        try:
            if not retry.BREAKER.allow():
                # circuit open: the device is considered down. Every
                # participant degrades on the spot — all states come
                # back via ``failed`` and continue on the host path.
                self.degraded_rounds += 1
                _cat.DEGRADED_ROUNDS_TOTAL.inc()
                obs.TRACER.mark("degraded_round", reason="breaker_open")
                for req in batch:
                    req.result = RoundResult(
                        None, None, [], list(req.states), 0.0, degraded=True
                    )
                return
            # merged round parameters: union/AND/MIN across participants
            host_ops = set()
            tape_replayers: dict = {}
            value_replayers: dict = {}
            prune_revert = True
            deadline = None
            for req in batch:
                host_ops |= set(req.host_ops or ())
                _merge_replayers(tape_replayers, req.tape_replayers)
                _merge_replayers(value_replayers, req.value_replayers)
                prune_revert = prune_revert and req.prune_revert
                if req.deadline is not None:
                    deadline = (
                        req.deadline if deadline is None
                        else min(deadline, req.deadline)
                    )

            # packing touches SMT terms / annotations -> host lock (I2)
            self.host_lock.acquire()
            try:
                bridge = DeviceBridge(
                    self.cfg,
                    host_ops=host_ops,
                    freeze_errors=True,
                    tape_replayers=tape_replayers,
                    value_replayers=value_replayers,
                    prune_revert=prune_revert,
                )
                bridge.ss_drains_by_job = self.ss_drains_by_job = {}
                with obs.phase("pack", jobs=len(batch)):
                    for req in batch:
                        bridge.job_id = req.job_id
                        for state in req.states:
                            if bridge._n_staged >= self.cfg.lanes:
                                req.failed.append(state)
                                continue
                            try:
                                bridge.stage(state)
                                req.packed.append(state)
                            except PackError as e:
                                log.debug("state stays on host path: %s", e)
                                req.failed.append(state)
                            except Exception as e:  # pragma: no cover
                                log.warning(
                                    "pack failed unexpectedly (%s); "
                                    "host continues", e
                                )
                                req.failed.append(state)
                if not any(req.packed for req in batch):
                    for req in batch:
                        req.result = RoundResult(
                            None, bridge, req.packed, req.failed, 0.0
                        )
                    return
            finally:
                self.host_lock.release()

            # the device round itself runs WITHOUT the host lock (I3):
            # jobs still in their host phase keep making progress and
            # can queue for the next round meanwhile. The guard retries
            # with backoff, keeps the breaker honest, and re-enters
            # bridge.finish() itself (re-runnable: staged numpy batch).
            counters = retry.RoundCounters()
            try:
                out, _hist, device_wall = retry.run_round_guarded(
                    bridge, self.cfg, want_stats=False,
                    deadline=deadline, counters=counters,
                )
            except retry.DeviceRoundError as e:
                # shared round degrades for every participant: packed
                # states move back through ``failed`` so each job's
                # exec_batch puts them on its own work list (same
                # put-back as a pack failure — nothing is dropped)
                log.warning("shared device round degraded to host: %s", e)
                self.degraded_rounds += 1
                _cat.DEGRADED_ROUNDS_TOTAL.inc()
                obs.TRACER.mark(
                    "degraded_round", reason="round_failed", seam=e.seam,
                )
                self.device_retries += counters.device_retries
                for req in batch:
                    req.result = RoundResult(
                        None, bridge, [], req.failed + req.packed, 0.0,
                        degraded=True, retries=counters.device_retries,
                        oom=e.oom,
                    )
                    req.packed = []
                return
            self.device_retries += counters.device_retries
            # the shared round's fused slots count once here; each job's
            # exec_batch counts the lane-steps of its own lanes
            fused = getattr(bridge, "fused_round_info", None)
            if fused:
                obs.count(_cat.DEVICE_LANE_SLOTS_TOTAL, fused["lane_slots"])

            resident = np.unique(
                np.asarray(out.job_id)[np.asarray(out.alive)]
            )
            resident = resident[resident > 0]
            self.rounds += 1
            if len(resident) > 1:
                self.shared_rounds += 1
            self.max_resident_jobs = max(
                self.max_resident_jobs, int(len(resident))
            )
            for req in batch:
                req.result = RoundResult(
                    out, bridge, req.packed, req.failed, device_wall,
                    retries=counters.device_retries,
                )
        except BaseException as e:  # pragma: no cover - round failure
            for req in batch:
                if not req.done:
                    req.error = e
        finally:
            with self._cv:
                for req in batch:
                    req.done = True
                self._cv.notify_all()


def _merge_replayers(into: dict, extra: Optional[dict]) -> None:
    """Union replayer dispatch tables, deduping hook entries by identity
    (detection modules are process singletons, so concurrent jobs carry
    the same bound methods)."""
    for key, hooks in (extra or {}).items():
        bucket = into.setdefault(key, [])
        for hook in hooks:
            if not any(hook is have for have in bucket):
                bucket.append(hook)
