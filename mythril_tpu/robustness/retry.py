"""Retry/degrade ladder for device rounds, and the circuit breaker.

One device round = pack upload (bridge.finish), the stepping loop
(backend._run_device) and the result download (transfer.batch_to_host).
Any of the three can die — OOM, XLA runtime error, a hung device call.
The ladder, in order:

  1. **retry** the whole round with bounded exponential backoff
     (transient runtime errors recover; an OOM skips straight to
     step 2 — the same-sized batch cannot suddenly fit);
  2. **shrink**: the caller halves its pack cap down the lane ladder
     (exec_batch ``seed_cap``) so later rounds ask the device for less;
  3. **breaker**: after ``BREAKER_THRESHOLD`` consecutive failed rounds
     the circuit opens — every resident lane's states are already back
     on their jobs' host work lists (the failed round's put-back), and
     all further device dispatch is skipped until a half-open trial
     after ``BREAKER_COOLDOWN_S``. Jobs continue HOST-ONLY and still
     complete, with ``degraded=true`` in their results.

Failures are classified, never silenced: exhausted retries raise
:class:`DeviceRoundError` carrying the seam name and the original
exception; callers degrade (put states back, count
``degraded_rounds``), they do not crash the job.
"""

import logging
import threading
import time

from mythril_tpu import obs
from mythril_tpu.obs import catalog as _cat
from mythril_tpu.robustness import faults

log = logging.getLogger(__name__)

# ladder step 1: total attempts = 1 + DEVICE_MAX_RETRIES
DEVICE_MAX_RETRIES = 2
BACKOFF_BASE_S = 0.05
BACKOFF_MAX_S = 2.0

# ladder step 3
BREAKER_THRESHOLD = 3
BREAKER_COOLDOWN_S = 60.0

# round watchdog: seconds of stepping-loop wall budget PER DEVICE ROUND.
# One guarded call used to be one device round; with the fused megakernel
# it is a K-round super-round, so the budget scales by the planned K —
# a K=32 super-round is 32 rounds of legitimate work, not a wedge. The
# clamp is cooperative (backend._run_device checks its deadline between
# dispatches and RUNNING lanes simply lift and continue), so expiry
# degrades throughput, never correctness.
ROUND_WATCHDOG_S = 30.0


class DeviceRoundError(RuntimeError):
    """A device round failed every attempt; the caller must continue the
    packed states on the host path."""

    def __init__(self, message: str, seam: str, cause: BaseException):
        super().__init__(message)
        self.seam = seam
        self.cause = cause
        self.oom = _is_oom(cause)


def _is_oom(exc: BaseException) -> bool:
    """Allocation failures are recognized by shape, not type: the real
    XLA error type is backend-specific, the injected one is ours."""
    if isinstance(exc, faults.DeviceOOM):
        return True
    text = str(exc)
    return "RESOURCE_EXHAUSTED" in text or "Out of memory" in text


class CircuitBreaker:
    """Consecutive-failure breaker with a timed half-open trial.

    ``allow()`` is True while closed; once ``threshold`` consecutive
    failures open it, ``allow()`` stays False until ``cooldown_s`` has
    passed, then admits trial rounds (half-open) — a success closes the
    breaker, a failure re-opens it for another cooldown. allow() claims
    nothing, so a caller that checks and then never runs a round cannot
    wedge the breaker; at service concurrency a few overlapping trials
    are harmless."""

    def __init__(self, threshold: int = BREAKER_THRESHOLD,
                 cooldown_s: float = BREAKER_COOLDOWN_S):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._lock = threading.Lock()
        self._failures = 0
        self._opened_at = None  # monotonic timestamp, None = closed
        self.trips = 0  # times the breaker opened (observability)

    def allow(self) -> bool:
        with self._lock:
            if self._opened_at is None:
                return True
            return time.monotonic() - self._opened_at >= self.cooldown_s

    @property
    def open(self) -> bool:
        with self._lock:
            return self._opened_at is not None

    def record_success(self) -> None:
        with self._lock:
            if self._opened_at is not None:
                obs.TRACER.mark("breaker_close")
                log.warning("device circuit breaker CLOSED (trial round ok)")
            self._failures = 0
            self._opened_at = None

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            if self._opened_at is not None:
                # failed half-open trial: restart the cooldown
                self._opened_at = time.monotonic()
                return
            if self._failures >= self.threshold:
                self._opened_at = time.monotonic()
                self.trips += 1
                obs.TRACER.mark("breaker_open", failures=self._failures)
                log.warning(
                    "device circuit breaker OPEN after %d consecutive "
                    "round failures: continuing HOST-ONLY (retry in %.0fs)",
                    self._failures, self.cooldown_s,
                )

    def reset(self) -> None:
        with self._lock:
            self._failures = 0
            self._opened_at = None
            self.trips = 0

    def state(self) -> str:
        with self._lock:
            if self._opened_at is None:
                return "closed"
            if time.monotonic() - self._opened_at >= self.cooldown_s:
                return "half-open"
            return "open"


# ONE breaker per process: single- and multi-tenant rounds, and the
# solver's device dispatches, all ride the same physical device
BREAKER = CircuitBreaker()


class RoundCounters:
    """Minimal counter sink for callers without a TpuBatchStrategy (the
    lane coordinator passes one per shared round)."""

    __slots__ = ("device_retries",)

    def __init__(self):
        self.device_retries = 0


def run_round_guarded(bridge, cfg, *, want_stats=False, deadline=None,
                      counters=None, sleep=time.sleep, fused_k=None):
    """One watchdogged device round: upload + step loop + download.

    Retries the whole chain with bounded exponential backoff
    (``counters.device_retries`` counts the extra attempts); an OOM
    stops retrying immediately. Success records into the breaker and
    returns ``(host_out, op_hist, device_wall)`` with ``device_wall``
    covering only the stepping loop of the successful attempt (download
    time is host transport, kept out of the device section as before).
    Exhaustion records a breaker failure and raises
    :class:`DeviceRoundError`.

    ``fused_k`` is the super-round depth the stepping loop plans to run
    (default: asked from the backend). The watchdog deadline scales by
    it — ``ROUND_WATCHDOG_S * fused_k`` — times the backend's planned
    mesh factor (fused mesh super-rounds additionally pay per-round
    collective latency), and is folded into (never past) the caller's
    ``deadline``, so a K-fused round gets K rounds' budget instead of
    tripping the single-round clamp.
    """
    from mythril_tpu.laser.tpu import backend, transfer

    if fused_k is None:
        fused_k = backend.planned_fused_k()
    watchdog_s = (
        ROUND_WATCHDOG_S * max(1, int(fused_k)) * backend.planned_mesh_factor()
    )
    attempts = 1 + DEVICE_MAX_RETRIES
    delay = BACKOFF_BASE_S
    last = None
    for attempt in range(attempts):
        if attempt:
            sleep(min(delay, BACKOFF_MAX_S))
            delay *= 2
            if counters is not None:
                counters.device_retries += 1
            _cat.DEVICE_RETRIES_TOTAL.inc()
            obs.TRACER.mark("device_retry", attempt=attempt)
        try:
            faults.fire(faults.DEVICE_ROUND)
            with obs.phase("transfer_up"):
                cb, st = bridge.finish()
            t0 = time.time()
            round_deadline = t0 + watchdog_s
            if deadline is not None:
                round_deadline = min(deadline, round_deadline)
            with obs.phase("device_round"):
                out, op_hist = backend._run_device(
                    cb, st, cfg, want_stats=want_stats,
                    deadline=round_deadline, bridge=bridge,
                )
            device_wall = time.time() - t0
            with obs.phase("transfer_down"):
                # mesh rounds compact per shard, so the download bucket
                # is per-shard too (set by _run_device on the bridge)
                out = transfer.batch_to_host(
                    out, n_shards=getattr(bridge, "mesh_n_shards", 1)
                )
            BREAKER.record_success()
            return out, op_hist, device_wall
        except Exception as e:
            last = e
            log.warning(
                "device round failed (attempt %d/%d, seam=%s): %s",
                attempt + 1, attempts, getattr(e, "seam", faults.DEVICE_ROUND), e,
            )
            if _is_oom(e):
                break
    BREAKER.record_failure()
    raise DeviceRoundError(
        "device round failed after %d attempt(s): %s" % (attempts, last),
        seam=getattr(last, "seam", faults.DEVICE_ROUND),
        cause=last,
    )
