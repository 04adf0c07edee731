"""The tpu-batch execution backend: a hybrid host/device work loop.

This is the integration seam the reference leaves at the strategy
boundary (mythril/laser/ethereum/strategy/__init__.py:6 iterator protocol
+ plugins/plugin.py:4 hooks): selecting ``--strategy tpu-batch`` replaces
the one-state-at-a-time host loop (svm.py:220 exec) with alternating
phases over the whole frontier:

  phase A (host): every state in the work list executes exactly ONE
    instruction through ``LaserEVM.execute_state`` — pre/post hooks fire,
    detection modules see the state, Transaction signals and VM
    exceptions are handled with full fidelity, and infeasible successors
    are filtered — the same per-instruction semantics as the reference's
    hot loop.
  phase B (device): the surviving frontier packs into a SoA StateBatch
    (laser/tpu/bridge.py) and the batched step kernel advances every lane
    in lockstep — forking on unhooked symbolic JUMPIs — until each lane
    freezes at the next host-relevant instruction: a hooked opcode, the
    call family, a halt (STOP/RETURN/REVERT/SELFDESTRUCT), or an error
    condition (replayed on host so exception handling and world-state
    revert semantics stay exact). Unpacked lanes rejoin the work list.

Opcodes with registered hooks always return to the host, so detection
modules observe every state they would have seen in the reference
pipeline. States the bridge cannot represent (PackError) simply stay on
the host path — the loop degrades gracefully to pure host execution.
"""

import logging
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import List, Optional

import numpy as np

from mythril_tpu.analysis import rewrite_pass, static_pass
from mythril_tpu.laser.evm.state.global_state import GlobalState
from mythril_tpu.laser.evm.strategy import BasicSearchStrategy
from mythril_tpu.laser.tpu.batch import (
    BatchConfig,
    RUNNING,
    default_env,
)
from mythril_tpu.laser.evm.plugins.signals import PluginSkipState
from mythril_tpu.laser.tpu.bridge import DeviceBridge, PackError
from mythril_tpu.laser.tpu.engine import run, run_with_stats
from mythril_tpu.laser.tpu import solver_cache, solver_jax, symtape, transfer
from mythril_tpu import obs
from mythril_tpu.exceptions import DeviceWarmupError
from mythril_tpu.obs import catalog as _cat
from mythril_tpu.robustness import retry as _retry
from mythril_tpu.support.opcodes import OPCODES

log = logging.getLogger(__name__)

# ops that end a transaction or leave the device model — always host-side.
# Block-context reads (TIMESTAMP/NUMBER/...) are NOT here: they retire on
# device as env-leaf tape nodes (symtape.ENV_LEAF_OP) that lift to the
# same symbols the host mints, with taint post-hooks replayed at lift.
# GAS stays device-modeled as the concrete per-lane gas counter.
_ALWAYS_HOST = (
    "STOP",
    "RETURN",
    "REVERT",
    "SUICIDE",
    "ASSERT_FAIL",
    "INVALID",
)

_NAME_TO_BYTE = {spec.name: byte for byte, spec in OPCODES.items()}


# module-level default so tests/CLI can swap in a differently-sized batch
# before SymExecWrapper constructs the strategy
DEFAULT_BATCH_CFG = BatchConfig(
    # 512 lanes: device forking fills lanes well beyond the staged
    # frontier now that whole transaction bodies retire on device
    # (+50% integrated throughput over 256 on the bench contract)
    lanes=512,
    stack_slots=32,
    memory_bytes=1024,
    calldata_bytes=256,
    # 32 slots: the resident storage plane — symbolic keccak-rooted keys
    # now land HERE (digest-probed, engine.py key_match) instead of
    # freeze-trapping the lane, so mapping-heavy contracts fill slots
    # that used to stay empty behind TRAP/TRAP_SS
    storage_slots=32,
    code_len=8192,
    tape_slots=192,
    path_slots=32,
    mem_sym_slots=8,
    # adaptive engagement (see BatchConfig): any nonempty frontier may
    # use the device, but only once the analysis has run 1.5 s — tiny
    # contracts finish on the host before that and never pay a device
    # round; long-running ones engage and let device forking amplify
    min_device_frontier=1,
    device_engage_after_s=1.5,
)


class TpuBatchStrategy(BasicSearchStrategy):
    """Marker strategy selecting the batched device backend.

    Iterating it behaves as BFS — used for the creation transaction and
    as the fallback when the device path is unavailable. Batch sizing is
    carried here so SymExecWrapper/CLI flags have a place to put it.
    """

    def __init__(self, work_list, max_depth, batch_cfg: Optional[BatchConfig] = None):
        super().__init__(work_list, max_depth)
        self.batch_cfg = batch_cfg or DEFAULT_BATCH_CFG
        # monotonic: a wall-clock step (NTP sync on remote VMs) must not
        # stretch or collapse the device_engage_after_s window
        self.created_at = time.monotonic()
        # precomputed deadline so engaged() costs one monotonic() call —
        # svm.exec polls it per instruction in the pre-engagement tier
        engage_after = self.batch_cfg.device_engage_after_s
        self._engage_deadline = (
            self.created_at + engage_after if engage_after else None
        )
        # fresh analysis, fresh triage state: an indecisive prior
        # contract must not disable triage for this one
        _TRIAGE_STRIKES[0] = 0
        _TRIAGE_UNKNOWN_TOKENS.clear()
        self.device_rounds = 0
        self.device_steps_retired = 0
        # storage-ring spill drains performed mid-round (lanes that would
        # have freeze-trapped at ring overflow before round 5)
        self.ss_drains = 0
        # JUMPI fork children suppressed on device because their taken
        # destination enters a static must-revert block (engine.py
        # prune_child; bench protocol field static_pruned_lanes)
        self.static_pruned_lanes = 0
        # robustness ladder accounting (bench protocol fields): extra
        # device-round attempts, and rounds that gave up on the device
        # and continued their packed states on the host path
        self.device_retries = 0
        self.degraded_rounds = 0
        # fused megakernel accounting (laser/tpu/megakernel.py): device
        # rounds retired inside fused super-round dispatches, host syncs
        # paid for them, per-dispatch round counts (the fused_k_p50/p95
        # bench distribution), lanes pruned on device without a lift,
        # and the cumulative device wall feeding device_residency_pct
        self.fused_rounds = 0
        self.fused_syncs = 0
        self.fused_k_samples: List[int] = []
        self.device_pruned_lanes = 0
        self.device_wall_s = 0.0
        # in-loop solve accounting (laser/tpu/inloop_solve.py): must-
        # UNSAT forks killed INSIDE the fused while_loop (no lift, no
        # decide_batch slot, super-round keeps running), and symbolic
        # keccak-rooted storage keys that resolved into the device
        # storage plane instead of freeze-trapping the lane
        self.in_loop_unsat_kills = 0
        self.storage_device_resolved = 0
        # fused-mesh accounting (docs/MESH.md): ICI work-steal exchanges
        # fired between super-round iterations, lanes they moved, and
        # the last observed per-shard frontier occupancy vector
        self.mesh_steal_events = 0
        self.mesh_steal_lanes = 0
        self.mesh_occupancy: List[int] = []
        # device-side SWC candidate sites: statically-flagged pcs
        # (CodeBank.swc_mask) some device lane actually visited this
        # analysis, keyed by SWC id. Candidates, not findings — the host
        # detection modules are the authoritative confirm at lift time
        self.swc_candidate_sites = {swc: 0 for swc in static_pass.SWC_MASK_BITS}
        # solver-cache accounting baseline: the cache is process-global
        # (verdicts legitimately outlive one analysis), so per-analysis
        # counters are deltas against the construction-time snapshot
        self._solver_base = solver_cache.GLOBAL.snapshot()
        # start compiling the device kernels NOW on a background thread:
        # the creation transaction and the first host rounds overlap the
        # XLA compile, and exec_batch switches to device rounds the
        # moment the kernels land. Blocking here instead would stall the
        # whole CLI behind a compile that can take minutes on a slow
        # machine.
        warmup_device_async(self.batch_cfg)

    def solver_stats(self) -> dict:
        """This analysis's solver-seam accounting (deltas against the
        construction-time snapshot of the process-global cache):
        solver_cache_hits / solver_cache_hit_rate / solver_time_s /
        z3_fallback_inflight_p95 — the bench protocol fields."""
        now = solver_cache.GLOBAL.snapshot()
        base = self._solver_base
        queries = now["queries"] - base["queries"]
        hits = now["hits"] - base["hits"]
        return {
            "solver_cache_hits": hits,
            "solver_cache_hit_rate": (hits / queries) if queries else 0.0,
            "solver_time_s": now["time_s"] - base["time_s"],
            "z3_fallback_inflight_p95": now["inflight_p95"],
            "static_unsat_seeds": now["static_unsat_seeds"]
            - base["static_unsat_seeds"],
        }

    @property
    def solver_cache_hits(self) -> int:
        return self.solver_stats()["solver_cache_hits"]

    @property
    def solver_time_s(self) -> float:
        return self.solver_stats()["solver_time_s"]

    def engaged(self) -> bool:
        """The scheduler's time gate: ONE definition shared by svm.exec
        (pre-engagement host tier + mid-phase handoff) and exec_batch
        (device rounds / feasibility dispatches)."""
        return (
            self._engage_deadline is None
            or time.monotonic() >= self._engage_deadline
        )

    def check_device(self) -> None:
        """Raise DeviceWarmupError if this analysis's device kernels
        failed to compile or run (backend.check_warmup)."""
        check_warmup(self.batch_cfg)

    def get_strategic_global_state(self) -> GlobalState:
        return self.work_list.pop(0)


def find_tpu_strategy(strategy) -> Optional[TpuBatchStrategy]:
    """Unwrap decorator strategies (BoundedLoops/Coverage) to the marker."""
    seen = set()
    while strategy is not None and id(strategy) not in seen:
        seen.add(id(strategy))
        if isinstance(strategy, TpuBatchStrategy):
            return strategy
        strategy = getattr(strategy, "super_strategy", None)
    return None


# opcodes whose skipped raw pre-hooks get re-fired at synthesized sites
# by the bridge (both from the ss_* storage event ring, in execution
# order — bridge._replay_segment_sites); a plugin's tape_replay_safe
# marker is only honored where such a channel exists — accepting it
# elsewhere would silently drop the hook
_RAW_REPLAY_OPS = frozenset({"SSTORE", "SLOAD"})

# opcodes whose POST-hooks (block-entry tracking, dependency pruner) can
# re-fire at lift over the reconstructed landing sequence: jumpdest-ring
# entries plus symbolic-branch fall-through sites
_RAW_POST_REPLAY_OPS = frozenset({"JUMP", "JUMPI"})


def _replayable_raw_post_hook(name: str, hooks) -> bool:
    """True when every post-hook on ``name`` is a plugin hook marked
    tape_replay_safe and a site-replay channel exists for the opcode."""
    if name not in _RAW_POST_REPLAY_OPS:
        return False
    return all(getattr(hook, "tape_replay_safe", False) for hook in hooks)


def _post_hooks_ok(laser, name: str) -> bool:
    """An opcode's post-hooks permit device retirement: none, or all
    replayable through the value channel or the raw site channel. ONE
    predicate shared by host_op_bytes (what retires) and
    tape_replayers_for (what replays) — if these drifted apart, an
    opcode could retire with its hooks silently dropped."""
    post = laser.post_hooks.get(name)
    return (
        not post
        or _replayable_post_hook(name, post)
        or _replayable_raw_post_hook(name, post)
    )

# opcodes with a VALUE-replay channel: they retire on device as env-leaf
# tape nodes (symtape.ENV_LEAF_OP / OP_ORIGIN), and a module's post-hook
# semantics (taint the pushed value) replay over the lifted value when
# the module declares the opcode in tape_replay_post_hooks
_VALUE_REPLAY_OPS = {
    "ORIGIN": symtape.OP_ORIGIN,
    "COINBASE": symtape.OP_COINBASE,
    "TIMESTAMP": symtape.OP_TIMESTAMP,
    "NUMBER": symtape.OP_NUMBER,
    "DIFFICULTY": symtape.OP_DIFFICULTY,
    "GASLIMIT": symtape.OP_GASLIMIT,
    "CHAINID": symtape.OP_CHAINID,
    "BASEFEE": symtape.OP_BASEFEE,
    "GASPRICE": symtape.OP_GASPRICE,
    "BLOCKHASH": symtape.OP_BLOCKHASH,
}


def _replayable_post_hook(name: str, hooks) -> bool:
    """True when every post-hook on ``name`` can replay over the lifted
    value: the opcode has a value-replay channel and every hook is a
    bound method of a module declaring it in tape_replay_post_hooks."""
    if name not in _VALUE_REPLAY_OPS:
        return False
    for hook in hooks:
        owner = getattr(hook, "__self__", None)
        if owner is None or name not in getattr(
            owner, "tape_replay_post_hooks", frozenset()
        ):
            return False
    return True


def _replayable_pre_hook(name: str, hooks) -> bool:
    """True when every pre-hook on ``name`` is batch-aware: either a
    bound method of a detection module declaring the opcode in
    tape_replay_hooks, or — on opcodes with a raw-hook replay channel —
    a plugin hook self-marked tape_replay_safe.

    A tape_replay_hooks declaration is a module-owned CONTRACT, not a
    routing request: the module asserts its pre-hook either (a) replays
    through an existing channel (per-node: ADD/SUB/MUL/EXP, site replay:
    JUMPI, event ring: SSTORE), (b) folds into its replay_tape_value on
    a value-channel opcode (BLOCKHASH's stale-query check), or (c) is
    safe to skip at device-retired sites because the condition it probes
    always traps anyway (JUMP/SLOAD window cases). Declaring an opcode
    with none of these holding silently drops the hook on device paths —
    keep the declaration next to the replay implementation."""
    for hook in hooks:
        if name in _RAW_REPLAY_OPS and getattr(hook, "tape_replay_safe", False):
            continue
        owner = getattr(hook, "__self__", None)
        if owner is None or name not in getattr(
            owner, "tape_replay_hooks", frozenset()
        ):
            return False
    return True


def host_op_bytes(laser) -> set:
    """Opcode bytes that must freeze-trap back to the host loop.

    An opcode whose every pre-hook is tape-replayable (and that has no
    post-hooks) retires on device; the bridge replays the hooks over the
    lifted tape at unpack time."""
    hooked = set()
    for name, hooks in laser.pre_hooks.items():
        if not hooks:
            continue
        if name == "*":
            return set(range(256))
        if _replayable_pre_hook(name, hooks) and _post_hooks_ok(laser, name):
            continue
        byte = _NAME_TO_BYTE.get(name)
        if byte is not None:
            hooked.add(byte)
    for name, hooks in laser.post_hooks.items():
        if not hooks:
            continue
        if name == "*":
            return set(range(256))
        if _post_hooks_ok(laser, name):
            continue
        byte = _NAME_TO_BYTE.get(name)
        if byte is not None:
            hooked.add(byte)
    for name in _ALWAYS_HOST:
        byte = _NAME_TO_BYTE.get(name)
        if byte is not None:
            hooked.add(byte)
    return hooked


def tape_replayers_for(laser) -> dict:
    """Replay dispatch for every opcode the hook exclusion in
    host_op_bytes lets retire on device: symtape node op ->
    [(module, opcode name)] for the arithmetic family (per-tape-node
    replay), plus the string key "JUMPI" for branch-site replay over the
    path tape (bridge._replay_jumpi_sites)."""
    from mythril_tpu.laser.tpu import symtape

    mapping = {
        "ADD": symtape.OP_ADD,
        "SUB": symtape.OP_SUB,
        "MUL": symtape.OP_MUL,
        "EXP": symtape.OP_EXP,
        "JUMPI": "JUMPI",
    }
    out: dict = {}
    for name, hooks in laser.pre_hooks.items():
        if name not in mapping or not hooks:
            continue
        if not _replayable_pre_hook(name, hooks) or not _post_hooks_ok(laser, name):
            continue
        for hook in hooks:
            owner = getattr(hook, "__self__", None)
            if owner is not None:
                out.setdefault(mapping[name], []).append((owner, name))
    # SLOAD/SSTORE sites replay the RAW skipped pre-hooks (modules and
    # marked plugin hooks alike) over the recorded storage event ring
    for raw_op in ("SSTORE", "SLOAD"):
        raw_hooks = laser.pre_hooks.get(raw_op, [])
        if (
            raw_hooks
            and _replayable_pre_hook(raw_op, raw_hooks)
            and _post_hooks_ok(laser, raw_op)
        ):
            out[raw_op] = list(raw_hooks)
    # block-entry tracking (dependency pruner): JUMP/JUMPI post-hooks
    # marked tape_replay_safe re-fire per reconstructed landing at lift
    entry_hooks: list = []
    for jump_op in ("JUMP", "JUMPI"):
        hooks = laser.post_hooks.get(jump_op, [])
        if hooks and _replayable_raw_post_hook(jump_op, hooks):
            for hook in hooks:
                if hook not in entry_hooks:
                    entry_hooks.append(hook)
    if entry_hooks:
        out["BLOCK_ENTRY"] = entry_hooks
    return out


def value_replayers_for(laser) -> dict:
    """Value-replay dispatch: symtape node op -> [(module, opcode name)]
    for every env-leaf opcode whose post-hook owners are batch-aware
    (tape_replay_post_hooks). The bridge fires these over the LIFTED
    value so taints land exactly where the host post-hook would put
    them; a module hooked on both sides (BLOCKHASH pre+post) registers
    once and replays both semantics in replay_tape_value."""
    out: dict = {}
    for name, tape_op in _VALUE_REPLAY_OPS.items():
        owners: list = []
        for hook in list(laser.post_hooks.get(name, ())) + list(
            laser.pre_hooks.get(name, ())
        ):
            owner = getattr(hook, "__self__", None)
            if (
                owner is not None
                and name in getattr(owner, "tape_replay_post_hooks", frozenset())
                and owner not in owners
            ):
                owners.append(owner)
        if owners:
            out[tape_op] = [(owner, name) for owner in owners]
    return out


# frontiers below this size are cheaper on the warm host CDCL than through
# a device dispatch; above it, one batched call decides every path
# condition. Deliberately WIDER than DEFAULT_BATCH_CFG.min_device_frontier
# (which gates device ROUNDS by width+time): a feasibility dispatch has no
# fork-amplification upside, so small batches should always stay on the
# host CDCL — measured r5, the suicide+origin row lost 0.2s of a 0.5s
# window to sub-8 feasibility batches before this floor
MIN_DEVICE_SOLVE_BATCH = 8

# search-flip budget per feasibility dispatch (static jit argnum: one
# budget = one kernel compile, so every call site must agree with the
# warmup). Deliberately SMALL: the round loop treats device SAT and
# UNKNOWN identically (the lane survives either way; settlement
# re-solves authoritatively), so all pruning throughput comes from the
# decision-free phase-1 propagation — phase-2 flips only buy SAT
# witnesses for warm-start model propagation, and r6 measured the 384-
# flip budget spending >60% of round wall time on unknown-heavy
# frontiers (BECStress) for verdicts the loop ignores
SOLVE_FLIPS = 64

# device-phase step budget per exec_batch round
DEVICE_STEP_BUDGET = 4096

# warmup bookkeeping: an Event per key marks a compile attempt in
# flight; membership in _warmup_done marks SUCCESS, and _warmup_errors
# holds the exception of a FAILED attempt. A compile is attempted exactly
# once per process; a failure is final and reaches the analysis as
# DeviceWarmupError (see check_warmup) instead of a silent host-only run.
_warmup_lock = threading.Lock()
_warmup_events: dict = {}
_warmup_done = set()
_warmup_errors: dict = {}


def _warmup_key(cfg: BatchConfig, want_stats: bool):
    """Warmup identity: the SHAPE-bearing fields plus the mesh mode.
    Scheduler policy knobs (min_device_frontier, device_engage_after_s)
    change no kernel, so configs differing only there share one compile;
    the mesh mode picks the sharded or the single-device executable, so
    each mode warms its own."""
    return (
        cfg._replace(min_device_frontier=0, device_engage_after_s=0.0),
        want_stats,
        _mesh_mode(),
    )

# The product path compiles on a background thread and lets host rounds
# overlap (see warmup_device_async). The test suite flips this to False
# (tests/conftest.py): tests assert device participation deterministically,
# so the strategy constructor must block until the kernels are ready.
WARMUP_ASYNC = True


def device_ready(cfg: BatchConfig, want_stats: bool = False) -> bool:
    """True once the kernels for this config compiled successfully."""
    return _warmup_key(cfg, want_stats) in _warmup_done


def check_warmup(cfg: BatchConfig, want_stats: bool = False) -> None:
    """Raise DeviceWarmupError if the warmup for this config failed. A
    warmup still compiling, or never started, passes."""
    error = _warmup_errors.get(_warmup_key(cfg, want_stats))
    if error is not None:
        # no jax here: the failure may be the backend itself not starting
        raise DeviceWarmupError("device warmup failed: %s" % error) from error


def warmup_pending() -> bool:
    """True while any warmup compile is still in flight on a background
    thread. The CLI checks this at exit: CPython finalization under a
    live native compile intermittently corrupts the heap, so callers
    that are done should hard-exit instead of tearing down."""
    with _warmup_lock:
        return any(not event.is_set() for event in _warmup_events.values())


def _claim_warmup(key):
    """Atomically register a compile attempt. Returns (event, owner):
    the caller owns the compile iff no attempt existed for this key."""
    with _warmup_lock:
        event = _warmup_events.get(key)
        if event is not None:
            return event, False
        event = _warmup_events[key] = threading.Event()
        return event, True


def warmup_device_async(cfg: BatchConfig, want_stats: bool = False) -> None:
    """Kick the compile off on a daemon thread and return immediately.

    exec_batch keeps running host rounds until device_ready flips, so a
    slow XLA compile costs the analysis nothing but the device speedup
    it would have had. A compile that FAILS is reported at the next
    check_warmup (exec_batch, end of sym_exec) and ends the analysis.

    With WARMUP_ASYNC off (the test suite) this compiles synchronously
    instead, so both production call sites dispatch through here."""
    if not WARMUP_ASYNC:
        warmup_device(cfg, want_stats)
        return
    key = _warmup_key(cfg, want_stats)
    event, owner = _claim_warmup(key)
    if owner:
        threading.Thread(
            target=_do_warmup,
            args=(key, event),
            name="tpu-warmup",
            daemon=True,
        ).start()


def warmup_device(cfg: BatchConfig, want_stats: bool = False) -> None:
    """Compile the step kernel (and the batched-solver kernel) for this
    batch config on an empty batch — every lane dead, so execution is a
    no-op but XLA compiles (and the persistent compile cache fills).
    Only the jit specialization the hot loop will use is compiled:
    ``want_stats`` selects the opcode-histogram variant (exec_batch
    warms it on demand when the profiler is enabled). Synchronous: on
    return the config is ready (device_ready true); a failed warmup
    raises DeviceWarmupError, now and for the life of the process."""
    key = _warmup_key(cfg, want_stats)
    event, owner = _claim_warmup(key)
    if owner:
        _do_warmup(key, event)
    else:
        event.wait()
    check_warmup(cfg, want_stats)


def _do_warmup(key, event) -> None:
    cfg, want_stats, _mode = key
    from mythril_tpu.robustness import faults

    try:
        # the fault harness targets the round loop's seams; the kernels
        # it guards must exist first, so warmup crosses them unarmed
        with faults.suppressed():
            _warm_kernels(cfg, want_stats)
        _warmup_done.add(key)
    except Exception as e:  # boundary: kept for check_warmup to raise
        log.error("device warmup failed: %s", e, exc_info=True)
        _warmup_errors[key] = e
    finally:
        event.set()


def _check_platform() -> None:
    """Refuse a CPU backend that nobody asked for. With JAX_PLATFORMS
    unset, jax starts its TPU backend quietly and falls back to the CPU
    when that fails (a chip held by another process, for one), so on a
    host with chips the "device" rounds would run on the CPU."""
    import jax

    from mythril_tpu.support.accelerators import host_chip_count, runs_on_cpu

    platform = jax.devices()[0].platform
    if platform == "cpu" and not runs_on_cpu():
        raise RuntimeError(
            "jax fell back to its CPU backend on a host with %d accelerator "
            "chip(s); is another process holding the chip? (set "
            "JAX_PLATFORMS=cpu to run on the CPU)" % host_chip_count()
        )


@contextmanager
def _warm_stage(stage: str):
    """One stage of the warmup: a ``warmup.<stage>`` span and its
    seconds on ``myth_warmup_s{stage}``."""
    t0 = time.perf_counter()
    with obs.TRACER.span("warmup." + stage, tid="warmup"):
        yield
    obs.count(_cat.WARMUP_S, time.perf_counter() - t0, stage)


def _warm_kernels(cfg: BatchConfig, want_stats: bool) -> None:
    from mythril_tpu.laser.tpu import ensure_compile_cache
    from mythril_tpu.laser.tpu.batch import batch_shapes, make_code_bank

    with _warm_stage("platform_upload"):
        # before anything compiles: a CPU fallback must not write XLA:CPU
        # entries into the accelerator's (unsuffixed) cache directory
        _check_platform()
        ensure_compile_cache()

        np_batch = {
            field: np.zeros(shape, dtype)
            for field, (shape, dtype) in batch_shapes(cfg).items()
        }
        # seed one element per upload group so warmup compiles the same
        # all-groups-present splitter variant (and tape bucket) the hot
        # loop uses, plus the download flatteners
        np_batch["memory"][0, 0] = 1
        np_batch["storage_used"][0, 0] = True
        np_batch["tape_len"][0] = 1
        np_batch["tape_op"][0, 0] = 1
        st = transfer.batch_to_device(np_batch, cfg)
        cb = make_code_bank([b"\x00"], cfg.code_len, host_ops=(), freeze_errors=True)
    # _run_device warms whichever loop the current policy selects
    # (normally the fused megakernel)
    with _warm_stage("fused_loop" if _fused_enabled() else "sync_loop"):
        out, _hist = _run_device(cb, st, cfg, want_stats=want_stats)
    # On the BACKGROUND-thread path also warm the synchronous slice
    # loop: the breaker's half-open trial rounds run it, and a trial
    # that pays the XLA compile inline would look exactly like the
    # wedged device it is probing for. A synchronous caller (the test
    # suite, warmup_device) blocks on this function, so it warms only
    # the selected loop — the fallback compiles lazily if the degrade
    # ladder ever runs.
    if WARMUP_ASYNC and _fused_enabled():
        with _warm_stage("sync_loop"):
            if want_stats:
                out, _ = run_with_stats(
                    cb, default_env(), out, max_steps=DEVICE_SLICE_STEPS
                )
            else:
                out = run(cb, default_env(), out, max_steps=DEVICE_SLICE_STEPS)
    with _warm_stage("download"):
        transfer.batch_to_host(out)
    from mythril_tpu.smt import terms as _terms

    warm_formula = [_terms.bool_eq(_terms.bv_var("!warmup", 8), _terms.bv_const(1, 8))]
    # warm the EXACT specializations the hot loop dispatches: the
    # feasibility flip budget (SOLVE_FLIPS — flips is a static
    # argnum, so a different budget is a different compile) at both
    # batch-ladder steps (remainder chunks and full chunks)
    with _warm_stage("solver_buckets"):
        solver_cache.warm_device(
            [warm_formula] * MIN_DEVICE_SOLVE_BATCH, flips=SOLVE_FLIPS
        )
        if not transfer.monomorphic():
            solver_cache.warm_device(
                [warm_formula] * solver_jax.MAX_BATCH, flips=SOLVE_FLIPS
            )


# lockstep steps between rebalance opportunities on a multi-device mesh
MESH_STEPS_PER_ROUND = 256


# mesh execution policy: "auto" shards over every visible accelerator
# device but stays single-device on the CPU backend (the virtual-8-CPU
# test mesh makes EVERY analysis pay SPMD partitioning cost otherwise);
# "on" forces sharding (the dedicated virtual-mesh integration tests),
# "sync" forces sharding but pins the legacy one-round-per-dispatch
# loop (the fused-mesh degrade tier, docs/MESH.md), "off" forces the
# single-device path. MYTHRIL_TPU_MESH overrides per process.
MESH_MODE = "auto"

# watchdog headroom multiplier while the mesh tier is active: fused
# super-rounds additionally pay psum/all-gather/all-to-all collective
# latency per round, which the single-device EMA never saw
MESH_WATCHDOG_FACTOR = 1.5


def _mesh_mode() -> str:
    mode = os.environ.get("MYTHRIL_TPU_MESH", MESH_MODE).lower()
    if mode not in ("auto", "on", "off", "sync"):
        log.warning("bad MYTHRIL_TPU_MESH=%r ignored", mode)
        mode = MESH_MODE
    return mode


def _mesh_tier(n_devices: int, platform: str) -> str:
    """Which mesh tier the next device round runs: "off" (single
    device), "sync" (legacy sharded slice loop), or "fused" (the
    shard_map megakernel with ICI work-stealing). The fused tier obeys
    the same breaker half-open degrade as the single-device megakernel:
    trial rounds probe the device through the simpler sync machinery."""
    mode = _mesh_mode()
    if n_devices < 2 or mode == "off":
        return "off"
    if mode == "auto" and platform == "cpu":
        return "off"
    if mode == "sync":
        return "sync"
    return "fused" if _fused_enabled() else "sync"


def planned_mesh_factor() -> float:
    """Watchdog multiplier for the tier the next round will run —
    robustness/retry.py folds this into the round watchdog alongside
    planned_fused_k() so mesh collective latency is never mistaken for
    a wedged device."""
    try:
        import jax

        devices = jax.devices()
        tier = _mesh_tier(len(devices), devices[0].platform)
    except Exception as e:  # pragma: no cover - device enumeration failed
        log.debug("mesh factor: device enumeration failed (%s)", e)
        return 1.0
    return MESH_WATCHDOG_FACTOR if tier != "off" else 1.0


# steps per deadline check: a full DEVICE_STEP_BUDGET round can take
# minutes on a slow backend, silently overshooting --execution-timeout;
# slicing bounds the overshoot to one slice's wall time
DEVICE_SLICE_STEPS = 512

# -- fused megakernel policy (laser/tpu/megakernel.py) -----------------
#
# "auto" fuses the single-device path and drops back to the synchronous
# slice loop while the circuit breaker is half-open — the trial round
# probes the device through the simpler machinery, and only a closed
# breaker re-admits the fused loop (docs/DEVICE_LOOP.md degrade ladder).
# "on"/"off" force the choice; MYTHRIL_TPU_FUSED overrides per process.
FUSED_MODE = "auto"
FUSED_K_MIN = 8
FUSED_K_MAX = 64
# super-round depth before any phase history exists to adapt from
FUSED_K_DEFAULT = 16

# steps per FUSED round (ISSUE 19): finer than the sync slice because
# the in-loop UNSAT screen, REVERT-prune and lane compaction all run at
# round boundaries — a doomed or halted lane stops burning step
# iterations at the next boundary, so shorter rounds waste less work
# (retired iterations = rounds x steps_per_round) and more rounds
# amortize per host sync. Traced work per round is fixed-shape either
# way; MYTHRIL_TPU_FUSED_STEPS pins it for bisection.
FUSED_STEPS_PER_ROUND = 256


def _fused_steps_per_round() -> int:
    env_v = os.environ.get("MYTHRIL_TPU_FUSED_STEPS")
    if env_v:
        try:
            return max(1, int(env_v))
        except ValueError:
            log.warning("bad MYTHRIL_TPU_FUSED_STEPS=%r ignored", env_v)
    return FUSED_STEPS_PER_ROUND

# EMA of device wall seconds per fused round — the adaptive-K
# controller's denominator, updated after every fused dispatch
_fused_round_cost_s = [0.0]


def _note_fused_round_cost(sample: float) -> None:
    prev = _fused_round_cost_s[0]
    _fused_round_cost_s[0] = sample if not prev else 0.5 * prev + 0.5 * sample


class _PhaseWalls:
    """The adaptive-K controller's numerator: a bounded window of the
    recent walls of the host phases one sync waits on. Kept here, not
    read from telemetry, so switching metrics off cannot change K."""

    PHASES = ("host_exec", "lift", "solve")
    CAP = 4096

    def __init__(self) -> None:
        self._walls = {p: deque(maxlen=self.CAP) for p in self.PHASES}

    def add(self, phase: str, wall: float) -> None:
        self._walls[phase].append(wall)

    def p95(self, phase: str) -> Optional[float]:
        walls = sorted(self._walls[phase])
        if not walls:
            return None
        return walls[min(len(walls) - 1, int(round(0.95 * (len(walls) - 1))))]


_HOST_WALLS = _PhaseWalls()


@contextmanager
def _host_phase(name: str, pid: int, **args):
    """``obs.phase`` for a phase the adaptive-K controller reads: its
    wall also feeds ``_HOST_WALLS``, telemetry on or off."""
    t0 = time.perf_counter()
    try:
        with obs.phase(name, pid=pid, **args):
            yield
    finally:
        _HOST_WALLS.add(name, time.perf_counter() - t0)


def _fused_enabled() -> bool:
    mode = os.environ.get("MYTHRIL_TPU_FUSED", FUSED_MODE).lower()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return _retry.BREAKER.state() != "half-open"


def _inloop_enabled() -> bool:
    """MYTHRIL_TPU_INLOOP_SOLVE=0 is the kill switch for the in-loop
    propagation-only UNSAT check (megakernel + inloop_solve): OFF runs
    the exact pre-ISSUE-19 fused loop (with_solve is a static jit arg,
    so the OFF specialization contains no solver code at all). Default
    on. The ON/OFF equivalence test pins identical issue sets."""
    return os.environ.get("MYTHRIL_TPU_INLOOP_SOLVE", "1").lower() not in (
        "0",
        "off",
        "false",
    )


def _pick_fused_k() -> int:
    """Adaptive super-round depth K.

    Stay on device while the host side of one sync under-fills the
    device budget: K ~ (host_exec + lift + solve p95 per sync) / (EMA
    device seconds per fused round), clamped to [FUSED_K_MIN,
    FUSED_K_MAX]. Until either side has history the default applies.
    MYTHRIL_TPU_FUSED_K pins K for benchmarking. K is passed TRACED
    into the megakernel, so adaptation never recompiles."""
    env_k = os.environ.get("MYTHRIL_TPU_FUSED_K")
    if env_k:
        try:
            return max(1, int(env_k))
        except ValueError:
            log.warning("bad MYTHRIL_TPU_FUSED_K=%r ignored", env_k)
    cost = _fused_round_cost_s[0]
    host = 0.0
    for ph in _PhaseWalls.PHASES:
        v = _HOST_WALLS.p95(ph)
        if v:
            host += v
    if not cost or not host:
        return FUSED_K_DEFAULT
    return int(min(FUSED_K_MAX, max(FUSED_K_MIN, round(host / cost))))


def planned_fused_k() -> int:
    """The K the next guarded round will run — robustness/retry.py
    scales its round watchdog by this so a fused super-round is never
    mistaken for a wedged device."""
    return _pick_fused_k() if _fused_enabled() else 1


def _drain_ss_rings(bridge, st):
    """Mid-round partial lift of full storage-event rings (VERDICT r4 #7).

    Lanes whose ONLY stop reason is ring overflow (status TRAP_SS) get
    their recorded events copied into the bridge's host-side spill chain
    — tape node ids stay valid for the rest of the round, so the events
    replay exactly at final lift, before the ring's — then resume on
    device with an empty ring (status RUNNING, ss_cnt 0). The spill
    token rides the ``spill_id`` plane so fork children inherit their
    prefix (reference behavior being preserved: every SLOAD/SSTORE fires
    its pre-hook exactly once, in order —
    mythril/laser/ethereum/instructions.py:1470).
    """
    import jax.numpy as jnp

    from mythril_tpu.laser.tpu.batch import RUNNING as _RUNNING
    from mythril_tpu.laser.tpu.batch import TRAP_SS as _TRAP_SS

    status = np.asarray(st.status)
    alive = np.asarray(st.alive)
    mask = alive & (status == _TRAP_SS)
    if not mask.any():
        return st
    lanes = np.nonzero(mask)[0]
    ss_cnt = np.asarray(st.ss_cnt)
    ss_pc = np.asarray(st.ss_pc)
    ss_key = np.asarray(st.ss_key)
    ss_val = np.asarray(st.ss_val)
    ss_is_load = np.asarray(st.ss_is_load)
    ss_jd = np.asarray(st.ss_jd)
    job_ids = np.asarray(st.job_id)
    spill_id = np.asarray(st.spill_id).copy()
    for lane in lanes:
        n = int(ss_cnt[lane])
        events = [
            (
                int(ss_pc[lane, j]),
                int(ss_key[lane, j]),
                int(ss_val[lane, j]),
                bool(ss_is_load[lane, j]),
                int(ss_jd[lane, j]),
            )
            for j in range(n)
        ]
        spill_id[lane] = bridge.spill_chain(int(spill_id[lane]), events)
        job = int(job_ids[lane])
        if job:
            bridge.ss_drains_by_job[job] = (
                bridge.ss_drains_by_job.get(job, 0) + 1
            )
    dev_mask = jnp.asarray(mask)
    return st._replace(
        status=jnp.where(dev_mask, _RUNNING, st.status),
        ss_cnt=jnp.where(dev_mask, 0, st.ss_cnt),
        spill_id=jnp.asarray(spill_id),
    )


def _run_device(cb, st, cfg, want_stats=False, deadline=None, bridge=None):
    """Run the packed batch to quiescence: single-device fast path, or —
    with more than one visible device — lane-sharded SPMD over a mesh with
    occupancy-gated all-to-all rebalancing (SURVEY §5 distributed backend;
    the production wiring of mesh.round_impl that the dryrun exercises).

    Returns ``(state, op_hist_or_None)``; the u32[256] retired-opcode
    histogram feeds the instruction profiler and is only produced on the
    single-device path (``want_stats``). ``deadline`` (time.time value)
    bounds the round for --execution-timeout honesty."""
    import jax

    from mythril_tpu.laser.tpu import mesh as mesh_lib
    from mythril_tpu.laser.tpu.batch import RUNNING as _RUNNING

    if bridge is not None:
        # reset the fused-round stash: a long-lived bridge (the shared
        # coordinator's) must not replay a PREVIOUS round's fused stats
        # into exec_batch when this round runs the sync/mesh path
        bridge.fused_round_info = None
        bridge.fused_pruned_visited = None
        bridge.mesh_n_shards = 1
    devices = jax.devices()
    n_shards = len(devices)
    tier = _mesh_tier(n_shards, devices[0].platform)
    if cfg.lanes % n_shards != 0:
        tier = "off"
    if tier == "off":
        if _fused_enabled():
            return _run_device_fused(
                cb, st, cfg, want_stats=want_stats, deadline=deadline,
                bridge=bridge,
            )
        import jax.numpy as jnp

        hist = None
        for _ in range(0, DEVICE_STEP_BUDGET, DEVICE_SLICE_STEPS):
            _cat.DEVICE_SLICES_TOTAL.inc()
            if want_stats:
                st, slice_hist = run_with_stats(
                    cb, default_env(), st, max_steps=DEVICE_SLICE_STEPS
                )
                hist = slice_hist if hist is None else hist + slice_hist
            else:
                st = run(cb, default_env(), st, max_steps=DEVICE_SLICE_STEPS)
            # slice boundary = host sync point: drain any lane stopped
            # purely by storage-ring overflow and resume it on device
            if bridge is not None:
                st = _drain_ss_rings(bridge, st)
            # the quiescence fetch blocks on the slice just dispatched, so
            # the deadline check AFTER it has absorbed the slice's device
            # time — overshoot is bounded by one slice
            if not bool(jnp.any(st.alive & (st.status == _RUNNING))):
                break
            if deadline is not None and time.time() > deadline:
                break
        return st, hist

    if bridge is not None:
        # per-shard download bucketing (transfer.batch_to_host) keys off
        # this: the mesh compaction leaves one dense prefix PER shard
        bridge.mesh_n_shards = n_shards
    mesh = mesh_lib.make_mesh(n_shards)
    st = mesh_lib.shard_batch(st, mesh)
    cb, env = mesh_lib.put_replicated((cb, default_env()), mesh)
    if tier == "fused":
        return _run_mesh_fused(
            mesh, n_shards, cb, env, st, want_stats=want_stats,
            deadline=deadline, bridge=bridge,
        )

    # sync degrade tier: one sharded round per dispatch. Quiescence and
    # rebalance gating both read the occupancy vector the PREVIOUS
    # dispatch computed on device — one i32[n_shards] fetch per round
    # instead of the full alive plane plus a separate occupancy pull.
    steps_done = 0
    occ = None
    while steps_done < DEVICE_STEP_BUDGET:
        _cat.DEVICE_SLICES_TOTAL.inc()
        do_reb = occ is not None and mesh_lib.should_rebalance_occ(occ)
        t0 = time.time()
        st, occ_dev = mesh_lib.sharded_round(
            cb,
            env,
            st,
            steps_per_round=MESH_STEPS_PER_ROUND,
            do_rebalance=do_reb,
            n_shards=n_shards,
        )
        occ = np.asarray(occ_dev)  # the one blocking fetch this round
        _cat.ROUND_PHASE_S.observe(time.time() - t0, "device_round_iter")
        obs.TRACER.cut(
            "mesh_round", "device_round_iter", shards=n_shards,
            rebalanced=bool(do_reb),
        )
        steps_done += MESH_STEPS_PER_ROUND
        if bridge is not None:
            drained = _drain_ss_rings(bridge, st)
            if drained is not st:
                # the replace built unsharded planes (and resumed TRAP_SS
                # lanes, so the fetched occ is stale); restore the lane
                # sharding and force a fresh occupancy next round
                st = mesh_lib.shard_batch(drained, mesh)
                occ = None
        if occ is not None and int(occ.sum()) == 0:
            break
        if deadline is not None and time.time() > deadline:
            break
    obs.TRACER.end_cut("mesh_round")
    return st, None


def _run_mesh_fused(
    mesh, n_shards, cb, env, st, want_stats=False, deadline=None, bridge=None
):
    """Fused MESH path: the megakernel super-round runs under shard_map
    over lane-sharded planes, with on-device ICI work-stealing between
    rounds (megakernel.run_fused_mesh, docs/MESH.md). Host-sync cadence
    and totals accounting mirror _run_device_fused; the extended info
    vector additionally carries steal counters and the per-shard
    frontier occupancy, which feed the myth_mesh_* gauges without any
    extra device fetch."""
    from mythril_tpu.laser.tpu import megakernel, mesh as mesh_lib

    k = _pick_fused_k()
    rounds_left = k
    hist = None
    pruned_visited = None
    with_solve = _inloop_enabled()
    # one pool per super-round, same cadence as the single-device path;
    # run_fused_mesh replicates it across shards (P() in_spec)
    pool = (
        transfer.pool_to_device(solver_cache.GLOBAL.build_inloop_pool())
        if with_solve
        else None
    )
    steps_per_round = _fused_steps_per_round()
    totals = {
        "k": k,
        "rounds": 0,
        "syncs": 0,
        "k_samples": [],
        "pruned_lanes": 0,
        "pruned_steps": 0,
        "pruned_static": 0,
        "inloop_kills": 0,
        "device_wall_s": 0.0,
        "lane_slots": 0,
        "n_shards": n_shards,
        "steal_events": 0,
        "steal_lanes": 0,
        "occupancy": [],
    }
    while rounds_left > 0:
        dispatch = rounds_left
        if deadline is not None:
            remaining = deadline - time.time()
            if remaining <= 0:
                break
            cost = _fused_round_cost_s[0]
            if cost > 0:
                dispatch = min(dispatch, max(1, int(remaining / cost)))
        _cat.DEVICE_SLICES_TOTAL.inc()
        t0 = time.time()
        span = obs.TRACER.begin(
            "fused_super_round", tid="device", max_rounds=int(dispatch),
            steps_per_round=steps_per_round, shards=n_shards,
        )
        fo = megakernel.run_fused_mesh(
            mesh,
            cb,
            env,
            st,
            max_rounds=dispatch,
            steps_per_round=steps_per_round,
            with_stats=want_stats,
            with_solve=with_solve,
            pool=pool,
        )
        st = fo.st
        stats = megakernel.decode_mesh_info(fo.info, n_shards)  # one fetch
        wall = time.time() - t0
        obs.TRACER.end(
            span, rounds=stats.rounds, n_alive=stats.n_alive,
            n_running=stats.n_running,
        )
        totals["syncs"] += 1
        totals["rounds"] += stats.rounds
        totals["lane_slots"] += stats.rounds * steps_per_round * st.pc.shape[0]
        totals["k_samples"].append(stats.rounds)
        totals["pruned_lanes"] += stats.pruned_lanes
        totals["pruned_steps"] += stats.pruned_steps
        totals["pruned_static"] += stats.pruned_static
        totals["inloop_kills"] += stats.inloop_kills
        totals["device_wall_s"] += wall
        if stats.inloop_kills:
            _cat.INLOOP_UNSAT_KILLS_TOTAL.inc(stats.inloop_kills)
        totals["steal_events"] += stats.steal_events
        totals["steal_lanes"] += stats.steal_lanes
        totals["occupancy"] = list(stats.occupancy)
        for shard, occ_v in enumerate(stats.occupancy):
            _cat.MESH_FRONTIER_OCCUPANCY.set(occ_v, str(shard))
        if stats.steal_events:
            _cat.MESH_STEAL_EVENTS_TOTAL.inc(stats.steal_events)
            _cat.MESH_STEAL_LANES_TOTAL.inc(stats.steal_lanes)
            obs.TRACER.cut(
                "mesh_steal", "steal", events=stats.steal_events,
                lanes=stats.steal_lanes,
            )
            obs.TRACER.end_cut("mesh_steal")
        if stats.pruned_lanes or stats.inloop_kills:
            pv = np.asarray(fo.pruned_visited)
            pruned_visited = (
                pv if pruned_visited is None else (pruned_visited | pv)
            )
        if want_stats:
            hist = fo.hist if hist is None else hist + fo.hist
        if stats.rounds:
            _note_fused_round_cost(wall / stats.rounds)
        rounds_left -= max(1, stats.rounds)
        resumed = False
        if bridge is not None:
            drained = _drain_ss_rings(bridge, st)
            if drained is not st:
                # resumed TRAP_SS lanes invalidate the fetched running
                # count; reshard and let the next dispatch re-derive it
                st = mesh_lib.shard_batch(drained, mesh)
                resumed = True
        if not resumed and stats.n_running == 0:
            # quiescence straight from the info vector — no extra fetch
            break
    if bridge is not None:
        bridge.fused_round_info = totals
        bridge.fused_pruned_visited = pruned_visited
    return st, hist


def _run_device_fused(cb, st, cfg, want_stats=False, deadline=None, bridge=None):
    """Single-device fused path: up to K device rounds retire inside ONE
    ``lax.while_loop`` dispatch (megakernel.run_fused) — fork, verdict
    pruning, and lane compaction all happen on device, and the host
    syncs once per dispatch instead of once per 512-step slice.

    The host loop here only re-dispatches when lanes frozen at storage-
    ring overflow (TRAP_SS) resume after a spill-chain drain, or when a
    deadline clamp cut the dispatch short — both are coarse-grained
    events, so ``rounds_per_host_sync`` stays ~K. Per-dispatch stats
    (rounds retired, lanes pruned on device, their step/coverage
    accumulators) ride back to exec_batch on the bridge."""
    from mythril_tpu.laser.tpu import megakernel

    k = _pick_fused_k()
    rounds_left = k
    hist = None
    pruned_visited = None
    with_solve = _inloop_enabled()
    # the pool is rebuilt once per super-round from the solver cache's
    # recorded must-UNSAT sets: facts learned during THIS super-round's
    # drain arrive next super-round (the in-loop check is a screen, not
    # a verdict authority — see docs/SOLVER.md)
    pool = (
        transfer.pool_to_device(solver_cache.GLOBAL.build_inloop_pool())
        if with_solve
        else None
    )
    steps_per_round = _fused_steps_per_round()
    totals = {
        "k": k,
        "rounds": 0,
        "syncs": 0,
        "k_samples": [],
        "pruned_lanes": 0,
        "pruned_steps": 0,
        "pruned_static": 0,
        "inloop_kills": 0,
        "device_wall_s": 0.0,
        "lane_slots": 0,
    }
    while rounds_left > 0:
        dispatch = rounds_left
        if deadline is not None:
            remaining = deadline - time.time()
            if remaining <= 0:
                break
            cost = _fused_round_cost_s[0]
            if cost > 0:
                # clamp the dispatch so the while_loop cannot overshoot
                # --execution-timeout by more than ~one round's wall
                dispatch = min(dispatch, max(1, int(remaining / cost)))
        _cat.DEVICE_SLICES_TOTAL.inc()
        t0 = time.time()
        # dispatch to fetch: the span covers the host's wait for the device
        span = obs.TRACER.begin(
            "fused_super_round", tid="device", max_rounds=int(dispatch),
            steps_per_round=steps_per_round,
        )
        fo = megakernel.run_fused(
            cb,
            default_env(),
            st,
            max_rounds=dispatch,
            steps_per_round=steps_per_round,
            with_stats=want_stats,
            with_solve=with_solve,
            pool=pool,
        )
        st = fo.st
        stats = megakernel.decode_info(fo.info)  # the one blocking fetch
        wall = time.time() - t0
        obs.TRACER.end(
            span, rounds=stats.rounds, n_alive=stats.n_alive,
            n_running=stats.n_running,
        )
        totals["syncs"] += 1
        totals["rounds"] += stats.rounds
        totals["lane_slots"] += stats.rounds * steps_per_round * st.pc.shape[0]
        totals["k_samples"].append(stats.rounds)
        totals["pruned_lanes"] += stats.pruned_lanes
        totals["pruned_steps"] += stats.pruned_steps
        totals["pruned_static"] += stats.pruned_static
        totals["inloop_kills"] += stats.inloop_kills
        totals["device_wall_s"] += wall
        if stats.inloop_kills:
            _cat.INLOOP_UNSAT_KILLS_TOTAL.inc(stats.inloop_kills)
        if stats.pruned_lanes or stats.inloop_kills:
            pv = np.asarray(fo.pruned_visited)
            pruned_visited = (
                pv if pruned_visited is None else (pruned_visited | pv)
            )
        if want_stats:
            hist = fo.hist if hist is None else hist + fo.hist
        if stats.rounds:
            _note_fused_round_cost(wall / stats.rounds)
        rounds_left -= max(1, stats.rounds)
        resumed = False
        if bridge is not None:
            drained = _drain_ss_rings(bridge, st)
            if drained is not st:
                # the drain resumed TRAP_SS lanes, so the info vector's
                # running count is stale — re-dispatch and re-derive it
                st = drained
                resumed = True
        if not resumed and stats.n_running == 0:
            # quiescence straight from the info vector — no extra fetch
            break
    if bridge is not None:
        bridge.fused_round_info = totals
        bridge.fused_pruned_visited = pruned_visited
    return st, hist


def filter_feasible(states: List[GlobalState]) -> List[GlobalState]:
    """Frontier-wide feasibility: consult the solver cache (verdict
    memo, UNSAT-prefix subsumption — laser/tpu/solver_cache.py), decide
    the misses in one batched device solve (unit propagation +
    ordered-DPLL search, laser/tpu/solver_jax.py, warm-started from
    parent-path models), and let whatever stays UNKNOWN proceed
    optimistically (unknown counts as possible — identical to
    Constraints.is_possible semantics; settlement re-solves
    authoritatively, and in service mode the async fallback pool's late
    UNSAT prunes the lane's descendants via subsumption next round).
    When the device did not run, an inline quick host check on the
    incremental CDCL prunes the frontier instead.

    Replaces the reference's one-Z3-call-per-forked-state pattern
    (mythril/laser/ethereum/svm.py:254, state/constraints.py:41).

    The device dispatch only engages after some warmup completed (the
    solver kernels compile alongside the step kernel): before that, a
    call here would pay the solver compile inline while the host CDCL
    answers lazily anyway."""
    undecided = [
        s for s in states if s.world_state.constraints._is_possible is None
    ]
    if undecided:
        # modest search budget: this is triage — propagation decides the
        # common selector/guard conditions instantly, and anything the
        # budget leaves open survives the round as possible
        # passive breaker read (not allow()): the half-open trial slot
        # belongs to the device ROUND path; solver dispatch stays off
        # until a trial round succeeds and closes the breaker
        use_device = (
            bool(_warmup_done)
            and len(undecided) >= MIN_DEVICE_SOLVE_BATCH
            and not _retry.BREAKER.open
        )
        sets = [
            [c.raw for c in s.world_state.constraints] for s in undecided
        ]
        hints = [getattr(s, "_solver_prefix_fps", None) for s in undecided]
        # static must-UNSAT seeds: lanes the bridge flagged because their
        # retired path took a branch direction the interval analysis
        # proves impossible (tables.jumpi_verdict) are decided UNSAT
        # without touching the memo or the device; a lane whose path
        # condition contains a term the rewrite stage already proved
        # self-contradictory (assume.note_unsat_term) joins them —
        # monotonicity makes any superset of an UNSAT term UNSAT
        static_unsat = [
            bool(getattr(s, "_static_unsat", False)) for s in undecided
        ]
        if rewrite_pass.known_unsat_count():
            for i, cs in enumerate(sets):
                if not static_unsat[i] and rewrite_pass.any_known_unsat(
                    t.uid for t in cs
                ):
                    static_unsat[i] = True
        # MUST value bounds on path condition words (bridge-attached from
        # tables.cond_intervals): interval-discharge seeds for stage 3
        interval_seeds = [
            getattr(s, "_interval_seeds", None) for s in undecided
        ]
        verdicts = solver_cache.GLOBAL.decide_batch(
            sets,
            use_device=use_device,
            flips=SOLVE_FLIPS,
            hints=hints,
            static_unsat=static_unsat if any(static_unsat) else None,
            interval_seeds=(
                interval_seeds
                if any(m is not None for m in interval_seeds)
                else None
            ),
            use="frontier",
        )
        for s, verdict in zip(undecided, verdicts):
            s.world_state.constraints.seed_feasibility(
                True if verdict is None else verdict
            )
    return [s for s in states if s.world_state.constraints.is_possible]


# consecutive all-unknown triage dispatches before the screen triage
# stops dispatching for the rest of the ANALYSIS (reset by each
# TpuBatchStrategy construction; list for mutability). Tokens whose
# prescreen came back unknown are memoized for the analysis so they are
# neither re-materialized nor re-dispatched (they hold strong refs, but
# the hazards' annotations keep those origins alive anyway).
_TRIAGE_MAX_STRIKES = 2
_TRIAGE_STRIKES = [0]
_TRIAGE_UNKNOWN_TOKENS: set = set()


def _triage_lazy_screens(states: List[GlobalState]) -> None:
    """Batch-screen the lifted frontier's unscreened parked findings in
    one device feasibility dispatch.

    Sibling lanes park the SAME finding (identical screen_key) under
    different path prefixes; one REPRESENTATIVE per group is solved —
    a provable-UNSAT representative is removed (what the eager host
    screen did, minus the ~73 ms solve), and a SAT verdict seeds the
    detector's sibling-collapse set so later host-path parks skip their
    eager screen too. Siblings are never culled on the representative's
    verdict (their path prefixes differ; UNSAT does not transfer) —
    they stay parked for transaction-end settlement, which re-solves
    authoritatively, so unknown verdicts are always safe to keep."""
    from mythril_tpu.analysis.potential_issues import PotentialIssuesAnnotation

    groups: dict = {}  # screen_key -> [(annotation, issue), ...]
    seen = set()
    for state in states:
        for ann in state.get_annotations(PotentialIssuesAnnotation):
            for issue in ann.potential_issues:
                if not issue.screened and id(issue) not in seen:
                    seen.add(id(issue))
                    key = issue.screen_key or ("anon", id(issue))
                    groups.setdefault(key, []).append((ann, issue))
    for members in groups.values():
        for _, issue in members:
            issue.screened = True

    # the decisiveness cutoff (and warmup state) gates ALL remaining
    # work, including the prescreen collection below: once the device
    # triage has proven indecisive on this workload's query population
    # (measured: BECToken's deep instances return 100% unknown from
    # UP+WalkSAT), later rounds must not keep paying the per-hazard
    # constraint-list copies either
    if not _warmup_done or _TRIAGE_STRIKES[0] >= _TRIAGE_MAX_STRIKES:
        return

    # settlement prescreens: modules exposing the protocol (integer's
    # _wrap_feasible cache) contribute (token, constraints) requests so
    # their transaction-end solves become cache hits. The loader list is
    # unfiltered — a module disabled for this run never tagged hazards,
    # so its collection is a cheap empty-annotation scan per state.
    prescreen = []  # (detector, token, constraints)
    from mythril_tpu.analysis.module.loader import ModuleLoader

    for module in ModuleLoader().get_detection_modules():
        collect = getattr(module, "batch_prescreen_requests", None)
        if collect is None:
            continue
        # skip holds tokens the module must not re-materialize
        # constraints for: already collected this round, or previously
        # triaged unknown (beyond the device solver's budget)
        skip = set(_TRIAGE_UNKNOWN_TOKENS)
        for state in states:
            try:
                requests = collect(state, skip)
            except Exception as e:  # pragma: no cover - prescreen best-effort
                log.debug("prescreen collect failed: %s", e)
                continue
            for token, constraints in requests:
                prescreen.append((module, token, constraints))

    # same economics as filter_feasible: tiny batches are not worth a
    # device dispatch — the parks go to settlement unscreened
    if len(groups) + len(prescreen) < MIN_DEVICE_SOLVE_BATCH:
        return
    reps = [members[0] for members in groups.values()]
    try:
        sets = [[c.raw for c in issue.constraints] for _, issue in reps]
        sets += [[c.raw for c in cons] for _, _, cons in prescreen]
        # host_fallback=False: unknown parks go to settlement, not to a
        # host solve — but memoized verdicts from the frontier path and
        # earlier rounds short-circuit here for free
        verdicts = solver_cache.GLOBAL.decide_batch(
            sets, use_device=True, flips=SOLVE_FLIPS, host_fallback=False,
            use="triage",
        )
    except Exception as e:  # pragma: no cover - device issues degrade
        log.warning("lazy screen triage failed: %s", e)
        return
    if any(v is not None for v in verdicts):
        _TRIAGE_STRIKES[0] = 0
    else:
        _TRIAGE_STRIKES[0] += 1
    for key, (ann, issue), verdict in zip(groups, reps, verdicts):
        if verdict is False:
            try:
                ann.potential_issues.remove(issue)
            except ValueError:  # pragma: no cover - shared annotation
                pass
        elif verdict is True and isinstance(key, tuple) and len(key) == 2:
            detector, fkey = key
            if fkey is not None and hasattr(detector, "_screen_key"):
                screened = getattr(detector, "_screened_sat", None)
                if screened is None:
                    screened = detector._screened_sat = set()
                screened.add(fkey)
    for (module, token, _), verdict in zip(
        prescreen, verdicts[len(reps):]
    ):
        if verdict is None:
            _TRIAGE_UNKNOWN_TOKENS.add(token)
            continue
        try:
            module.seed_prescreen(token, bool(verdict))
        except Exception as e:  # pragma: no cover - prescreen best-effort
            log.debug("prescreen seed for %s failed: %s", module, e)


def _apply_loop_bound(laser, states: List[GlobalState]) -> List[GlobalState]:
    """Enforce -b on device-explored loops: host-side the bound fires when
    a state is SELECTED at a JUMPDEST, but lanes that looped on device
    come back frozen at a trap op, so the selection-time check never sees
    them. Run the same repeating-cycle test on the lifted jumpdest traces
    here and drop states beyond the bound."""
    from mythril_tpu.laser.evm.strategy.extensions.bounded_loops import (
        BoundedLoopsStrategy,
        JumpdestCountAnnotation,
    )
    from mythril_tpu.laser.evm.transaction.transaction_models import (
        ContractCreationTransaction,
    )

    bounded = laser.strategy
    while bounded is not None and not isinstance(bounded, BoundedLoopsStrategy):
        bounded = getattr(bounded, "super_strategy", None)
    if bounded is None:
        return states

    kept = []
    for state in states:
        annotations = list(state.get_annotations(JumpdestCountAnnotation))
        trace = annotations[0].trace if annotations else []
        if len(trace) >= 4:
            count = _suffix_cycle_count(trace)
            bound = bounded.bound
            if isinstance(state.current_transaction, ContractCreationTransaction):
                bound = max(8, bound)
            if count > bound:
                bounded.skipped += 1
                continue
        kept.append(state)
    return kept


def _suffix_cycle_count(trace: List[int]) -> int:
    """Largest number of contiguous repeats of any cycle ending the trace.

    The host strategy's pair-distance heuristic
    (strategy/extensions/bounded_loops.py) assumes one entry PER
    INSTRUCTION; the device ring records jump landings only, so the repeat
    count is computed directly on suffix periods here."""
    n = len(trace)
    best = 1
    for period in range(1, n // 2 + 1):
        window = trace[n - period :]
        repeats = 1
        while (
            n - (repeats + 1) * period >= 0
            and trace[n - (repeats + 1) * period : n - repeats * period] == window
        ):
            repeats += 1
        if repeats > best:
            best = repeats
    return best


def exec_batch(laser, track_gas=False) -> Optional[List[GlobalState]]:
    """Drain the work list through alternating host/device phases.

    With ``track_gas`` (the concolic/conformance mode, reference surface
    svm.py exec(track_gas=True)) the states that halt are collected and
    returned so gas bounds and post-state can be asserted."""
    strategy = find_tpu_strategy(laser.strategy)
    cfg = strategy.batch_cfg
    host_ops = host_op_bytes(laser)
    replayers = tape_replayers_for(laser)
    val_replayers = value_replayers_for(laser)
    # static must-revert fork pruning is sound only when the suppressed
    # child is truly unobservable: outermost reverting frames are
    # discarded by transaction finalization, but a REVERT hook would
    # have fired on the pruned path, and track_gas asserts gas totals
    # the pruned path never accumulates — gate on both
    prune_revert = not track_gas and not (
        laser.pre_hooks.get("REVERT") or laser.post_hooks.get("REVERT")
    )
    # multi-tenant seam: the analysis service installs a JobContext on
    # the laser (service/lanes.py via SymExecWrapper's pre_exec_hook);
    # when present, device rounds are shared with other in-flight jobs
    # through the lane coordinator and this job's lanes are identified
    # by the job_id plane
    job_ctx = getattr(laser, "job_ctx", None)
    if job_ctx is not None:
        # fork headroom scales with the jobs sharing the lane axis
        share = job_ctx.coordinator.active_jobs()
        seed_cap = max(1, cfg.lanes // (2 * share))
    else:
        seed_cap = max(1, cfg.lanes // 2)  # leave headroom for device forks
    final_states: List[GlobalState] = []
    budget_deadline = (
        laser.time.timestamp() + laser.execution_timeout
        if laser.execution_timeout
        else None
    )
    want_stats = laser.iprof is not None
    if want_stats:
        # profiled runs use the histogram specialization of the run loop;
        # start compiling it alongside the plain variant
        warmup_device_async(cfg, want_stats=True)

    # observability: jobs render as trace process rows (pid 0 =
    # single-tenant), rounds as sequential "cut" spans that survive the
    # loop body's continue/early-return paths (obs/trace.py)
    _pid = job_ctx.job_id if job_ctx is not None else 0
    _round_no = 0

    while laser.work_list:
        _round_no += 1
        obs.TRACER.cut("round", "round", pid=_pid, round=_round_no)
        if budget_deadline is not None and time.time() >= budget_deadline:
            log.debug("Hit execution timeout in tpu-batch loop, returning.")
            # keep the in-flight frontier: the host loop's timeout path
            # returns the currently selected state too
            return final_states + laser.work_list[:] if track_gas else None
        if job_ctx is not None and job_ctx.cancelled():
            # cancellation mirrors the deadline path: the in-flight
            # frontier stays on the work list, never dropped
            log.debug("job %d cancelled in tpu-batch loop", job_ctx.job_id)
            return final_states + laser.work_list[:] if track_gas else None

        # ---------------- phase A: one host instruction per state.
        # Selection goes through the STRATEGY iterator, not the raw work
        # list: decorator strategies (BoundedLoops jumpdest-trace bounds,
        # Coverage preference) filter and annotate at selection time
        # exactly as in the host loop (reference svm.py exec).
        pending = list(laser.strategy)
        produced: List[tuple] = []  # (state, new_states, op_code)
        with _host_phase("host_exec", _pid, states=len(pending)):
            for global_state in pending:
                try:
                    new_states, op_code = laser.execute_state(global_state)
                except NotImplementedError:
                    log.debug("Encountered unimplemented instruction")
                    continue
                produced.append((global_state, new_states, op_code))
        # pre-engagement the analysis must behave like the pure host
        # loop — including NO device feasibility dispatches (measured
        # r5: they alone cost the suicide+origin row ~25%); the survivor
        # loop below performs the same per-state is_possible check the
        # batched call would have seeded
        engaged = strategy.engaged()
        if engaged:
            # feasibility for the whole successor frontier in one call
            with _host_phase("solve", _pid):
                filter_feasible(
                    [s for _, states, _ in produced for s in states]
                )
        survivors = []
        for global_state, new_states, op_code in produced:
            new_states = [
                state
                for state in new_states
                if state.world_state.constraints.is_possible
            ]
            laser.manage_cfg(op_code, new_states)
            if new_states:
                survivors.extend(new_states)
            elif track_gas:
                final_states.append(global_state)
            laser.total_states += len(new_states)
        if not survivors:
            continue

        # ---------------- phase B: batched device rounds.
        # Until the background warmup lands the compiled kernels, phase A
        # keeps making host progress — none of it wasted — and the device
        # joins mid-analysis the moment it is ready. Narrow frontiers
        # also stay host-side (min_device_frontier): packing a handful
        # of states through a device round costs more than executing
        # them directly, so the device engages when exploration widens.
        if not device_ready(cfg, want_stats):
            check_warmup(cfg, want_stats)
            laser.work_list.extend(survivors)
            continue
        if len(survivors) < cfg.min_device_frontier or not engaged:
            laser.work_list.extend(survivors)
            continue
        if job_ctx is None and _retry.BREAKER.state() == "open":
            # circuit open (cooldown running): the device is considered
            # down — this round continues host-only. The shared-round
            # path makes the same call inside the lane coordinator.
            laser.work_list.extend(survivors)
            strategy.degraded_rounds += 1
            _cat.DEGRADED_ROUNDS_TOTAL.inc()
            obs.TRACER.mark("degraded_round", pid=_pid, reason="breaker_open")
            continue
        to_pack = survivors[:seed_cap]
        overflow = survivors[seed_cap:]
        laser.work_list.extend(overflow)

        if job_ctx is not None:
            # shared round: this job's frontier rides the same device
            # batch as every other gathered job's (service/lanes.py);
            # ownership comes back on the job_id plane
            res = job_ctx.coordinator.run_round(
                job_id=job_ctx.job_id,
                states=to_pack,
                host_ops=host_ops,
                tape_replayers=replayers,
                value_replayers=val_replayers,
                prune_revert=prune_revert,
                deadline=budget_deadline,
                cancel_event=job_ctx.cancel_event,
            )
            if res is None:
                # cancelled while the round was pending: restore the
                # in-flight states exactly like the deadline put-back —
                # cancellation must not drop them
                laser.work_list.extend(to_pack)
                return final_states + laser.work_list[:] if track_gas else None
            laser.work_list.extend(res.failed)
            packed_states = res.packed
            strategy.device_retries += res.retries
            if res.degraded:
                # the shared round gave up on the device; every state is
                # back in res.failed and continues on the host path
                strategy.degraded_rounds += 1
                if res.oom:
                    seed_cap = max(1, seed_cap // 2)
            if res.out is None or not packed_states:
                continue
            bridge = res.bridge
            out = res.out  # already host-side
            op_hist = None
            device_wall = res.device_wall
            job_mask = np.asarray(out.job_id) == job_ctx.job_id
        else:
            bridge = DeviceBridge(
                cfg,
                host_ops=host_ops,
                freeze_errors=True,
                tape_replayers=replayers,
                value_replayers=val_replayers,
                prune_revert=prune_revert,
            )
            packed_states = []
            with obs.phase("pack", pid=_pid, states=len(to_pack)):
                for state in to_pack:
                    try:
                        bridge.stage(state)
                        packed_states.append(state)
                    except PackError as e:
                        log.debug("State stays on host path: %s", e)
                        laser.work_list.append(state)
                    except Exception as e:  # pragma: no cover - pack bugs degrade
                        # an unexpected staging failure must not kill the whole
                        # analysis: the state is untouched (stage wipes the lane
                        # on failure), so the host path continues it exactly
                        log.warning(
                            "pack failed unexpectedly (%s); host continues", e
                        )
                        laser.work_list.append(state)
            if not packed_states:
                continue

            if not _retry.BREAKER.allow():
                # raced into an open/claimed breaker after packing: the
                # staged states are untouched host-side, continue them
                laser.work_list.extend(packed_states)
                strategy.degraded_rounds += 1
                _cat.DEGRADED_ROUNDS_TOTAL.inc()
                obs.TRACER.mark(
                    "degraded_round", pid=_pid, reason="breaker_claimed"
                )
                continue
            try:
                # guarded round: retries with backoff inside (counted on
                # strategy.device_retries), breaker bookkeeping, and the
                # device wall covering only the stepping loop (advisor
                # r3: the download is host transport cost)
                out, op_hist, device_wall = _retry.run_round_guarded(
                    bridge,
                    cfg,
                    want_stats=want_stats,
                    deadline=budget_deadline,
                    counters=strategy,
                )
            except _retry.DeviceRoundError as e:
                # degrade, never die: the staged states still exist on
                # the host side — put them back and keep executing.
                # An OOM additionally halves the pack cap (ladder step
                # 2): the next round asks the device for less.
                log.warning("device round degraded to host path: %s", e)
                strategy.degraded_rounds += 1
                _cat.DEGRADED_ROUNDS_TOTAL.inc()
                obs.TRACER.mark(
                    "degraded_round", pid=_pid, reason="round_failed",
                    seam=e.seam,
                )
                laser.work_list.extend(packed_states)
                if e.oom:
                    seed_cap = max(1, seed_cap // 2)
                continue
            job_mask = None
        if op_hist is not None and laser.iprof is not None:
            hist = np.asarray(op_hist)
            counts = {
                (
                    OPCODES[op_byte].name
                    if op_byte in OPCODES
                    else f"0x{op_byte:02x}"
                ): int(n)
                for op_byte, n in enumerate(hist)
                if n
            }
            if counts:
                laser.iprof.record_device_round(counts, device_wall)
        strategy.device_rounds += 1
        _cat.DEVICE_ROUNDS_TOTAL.inc()
        strategy.device_wall_s += device_wall
        # fused super-round accounting (megakernel.py, stashed on the
        # bridge by _run_device_fused): rounds retired per host sync and
        # the on-device prune accumulators. In a SHARED round the prune
        # accumulators cannot be split per job (the pruned lanes' job
        # ids died with them), so only the single-tenant path folds them
        # into counters/coverage; the shared path loses a little metric
        # attribution, never correctness.
        fused = getattr(bridge, "fused_round_info", None)
        fused_pv = getattr(bridge, "fused_pruned_visited", None)
        if fused:
            strategy.fused_rounds += fused["rounds"]
            strategy.fused_syncs += fused["syncs"]
            strategy.fused_k_samples.extend(fused["k_samples"])
            strategy.mesh_steal_events += fused.get("steal_events", 0)
            strategy.mesh_steal_lanes += fused.get("steal_lanes", 0)
            if fused.get("occupancy"):
                strategy.mesh_occupancy = list(fused["occupancy"])
            if job_ctx is not None and fused["rounds"]:
                # S1: a K-fused super-round must not silently widen the
                # checkpoint cadence — credit the journal so the next
                # stop_sym_trans snapshots once credits cover one period
                from mythril_tpu.robustness import checkpoint as _ckpt

                _ckpt.credit_rounds(job_ctx.job_id, fused["rounds"])
        # harvest split: in a shared round only the lanes stamped with
        # THIS job's id feed its counters/coverage — other tenants'
        # lanes (alive or dead) belong to their own accounting
        own_alive = np.asarray(out.alive)
        if job_mask is None:
            _steps = int(np.asarray(out.steps).sum())
            strategy.ss_drains += bridge.ss_drain_count
            if fused:
                _steps += fused["pruned_steps"]
                obs.count(_cat.DEVICE_LANE_SLOTS_TOTAL, fused["lane_slots"], pid=_pid)
                strategy.static_pruned_lanes += fused["pruned_static"]
                strategy.device_pruned_lanes += fused["pruned_lanes"]
                strategy.in_loop_unsat_kills += fused.get("inloop_kills", 0)
            # storage keys resolved on device this round: symbolic-key
            # entries in the enlarged storage plane that previously froze
            # the lane (TRAP) instead of probing
            _sdr = int(
                (np.asarray(out.skey_sym)[own_alive] > 0).sum()
            )
            if _sdr:
                strategy.storage_device_resolved += _sdr
                _cat.STORAGE_DEVICE_RESOLVED_TOTAL.inc(_sdr)
        else:
            own_alive = own_alive & job_mask
            _steps = int(np.asarray(out.steps)[job_mask].sum())
            strategy.ss_drains += bridge.ss_drains_by_job.get(
                job_ctx.job_id, 0
            )
            fused_pv = None
        strategy.device_steps_retired += _steps
        obs.count(_cat.DEVICE_STEPS_TOTAL, _steps, pid=_pid)
        strategy.static_pruned_lanes += int(
            np.asarray(out.static_pruned)[own_alive].sum()
        )

        # measurement parity: instructions retired on device feed the same
        # coverage accounting the host's execute_state hook does
        with obs.phase("harvest", pid=_pid):
            if laser._device_coverage_hooks:
                visited = np.asarray(out.visited)
                code_ids = np.asarray(out.code_id)
                for code_id, code_bytes in enumerate(bridge.codes):
                    lanes_mask = own_alive & (code_ids == code_id)
                    # lanes pruned ON DEVICE (megakernel revert prune)
                    # left no lane to read — their coverage rides the
                    # fused loop's pruned_visited union instead
                    union = None
                    if lanes_mask.any():
                        union = visited[lanes_mask].any(axis=0)
                    if fused_pv is not None and code_id < fused_pv.shape[0]:
                        row = fused_pv[code_id]
                        union = row if union is None else (union | row)
                    if union is None:
                        continue
                    offsets = np.nonzero(union)[0]
                    if offsets.size == 0:
                        continue
                    for hook in laser._device_coverage_hooks:
                        hook(code_bytes.hex(), offsets.tolist())

            # device-side SWC candidate masks: join the static pass's
            # per-pc swc_mask plane (lifted into CodeBank.swc_mask)
            # against the pcs device lanes of THIS job actually visited.
            # Candidates only — the host detection modules remain the
            # authoritative confirm; this feeds bench/service counters,
            # never a report.
            swc_visited = np.asarray(out.visited)
            swc_code_ids = np.asarray(out.code_id)
            for code_id, code_bytes in enumerate(bridge.codes):
                lanes_mask = own_alive & (swc_code_ids == code_id)
                has_pruned = (
                    fused_pv is not None
                    and code_id < fused_pv.shape[0]
                    and fused_pv[code_id].any()
                )
                if not lanes_mask.any() and not has_pruned:
                    continue
                try:
                    mask = static_pass.analyze(code_bytes).swc_mask
                except Exception as e:  # pragma: no cover - analysis degrade
                    log.debug("swc harvest: static pass failed: %s", e)
                    continue
                width = min(len(mask), swc_visited.shape[1])
                union = swc_visited[lanes_mask][:, :width].any(axis=0)
                if has_pruned:
                    union = union | fused_pv[code_id][:width]
                hit = mask[:width][union]
                if hit.size == 0:
                    continue
                for swc, bit in static_pass.SWC_MASK_BITS.items():
                    strategy.swc_candidate_sites[swc] += int(
                        np.count_nonzero(hit & bit)
                    )

        status = np.asarray(out.status)
        resumed_states = []
        # deferred findings collected during hook replay park UNSCREENED
        # (potential_issues.LAZY_SCREEN); the whole frontier's screens
        # then run as one batched device feasibility call below instead
        # of one ~73 ms host solve per finding per lane
        from mythril_tpu.analysis import potential_issues as _pi

        _pi.LAZY_SCREEN = True
        try:
            with _host_phase("lift", _pid):
                for lane in range(own_alive.shape[0]):
                    if not own_alive[lane]:
                        continue
                    if status[lane] == RUNNING:
                        # step budget exhausted mid-flight: unpack and
                        # continue on whatever path the next iteration
                        # picks
                        pass
                    try:
                        resumed = bridge.unpack_lane(out, lane)
                    except PluginSkipState:
                        # block-entry replay pruned the state (dependency
                        # pruner: re-entering cannot observe new writes)
                        log.debug("lane %d pruned at lifted block entry", lane)
                        continue
                    except Exception as e:  # pragma: no cover - lift bugs
                        log.warning("unpack failed for lane %d: %s", lane, e)
                        continue
                    resumed_states.append(resumed)
        finally:
            _pi.LAZY_SCREEN = False
        with obs.phase("triage", pid=_pid):
            _triage_lazy_screens(resumed_states)
        with _host_phase("solve", _pid):
            feasible = filter_feasible(resumed_states)
        laser.work_list.extend(_apply_loop_bound(laser, feasible))
        # device-born forks add to the explored-state count — including
        # forks that lived and died entirely on device (revert prune and
        # in-loop must-UNSAT kills: a device-killed fork counts exactly
        # like a host filter_feasible kill would have)
        _born_dead = (
            fused["pruned_lanes"] + fused.get("inloop_kills", 0)
            if fused and job_mask is None
            else 0
        )
        laser.total_states += max(
            0, int(own_alive.sum()) + _born_dead - len(packed_states)
        )
    obs.TRACER.end_cut("round", pid=_pid)
    if strategy.device_rounds == 0 and not device_ready(cfg, want_stats):
        log.info(
            "analysis drained before the device kernels finished "
            "compiling; all execution stayed on the host path"
        )
    return final_states if track_gas else None
