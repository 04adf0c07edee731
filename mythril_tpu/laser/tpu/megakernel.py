"""Device-resident fused round loop: step -> prune -> compact, K times.

The integrated pipeline's dominant cost is the seam, not the stepping:
after every device round the backend returns to host for quiescence
checks, ring drains, lift and re-pack (BENCH_r05: ~350x gap between the
raw step kernel and end-to-end throughput). This module keeps the batch
resident by fusing K symbolic-execution rounds into ONE ``lax.while_loop``
dispatch:

  round body  = ``steps_per_round`` engine steps (forks included — the
                step kernel's free-lane cumsum already places children)
  then prune  = kill lanes frozen at an outermost REVERT while static
                must-revert pruning is armed (``CodeBank.prune_revert``)
  then compact = stable-sort the lanes so the alive frontier is a prefix

  loop cond   = rounds < max_rounds  AND  any lane still RUNNING

The cond is the per-lane ``needs_host`` reduction from the design note:
a lane is RUNNING, halted, or frozen at a host-routed op (TRAP /
TRAP_SS).  ``~any(RUNNING)`` is exactly "every alive lane needs the host
or is done", so the loop exits to host only when the frontier drains or
every survivor is waiting on a host op — never one round per sync.

Prune soundness: with ``prune_revert`` armed the backend guaranteed no
REVERT pre/post hooks exist and gas is not tracked.  An outermost frame
that reverts is discarded by the host's ``_finalize_transaction`` with
``committed = False`` — no ``check_potential_issues`` settlement, no
open world state — and every hook-replayed finding parks on the
discarded state (settlement detectors like integer settle at
STOP/RETURN, which a lane frozen AT the REVERT byte can never reach).
Killing the lane on device therefore produces the same observable
result as lifting it, replaying its hooks, and watching the host throw
the frame away — minus the lift.  The lane's coverage/counter planes
are folded into the fused-loop accumulators below so measurement parity
survives the skip.

Compaction soundness: every ``StateBatch`` plane is lane-major
(``batch_shapes``: leading dim L), and the host lift resolves all
staged metadata through the ``seed_id``/``spill_id``/``job_id`` PLANES,
never through raw lane positions — so a lane permutation is invisible
to the bridge.  A stable argsort on ``~alive`` keeps relative lane
order among survivors (the S2 property test pins this down) and makes
the alive frontier a dense prefix, which later forks refill and the
host download can slice.

In-loop-UNSAT soundness (ISSUE 19): with ``with_solve`` armed, each
round additionally kills RUNNING lanes whose path condition
``inloop_solve.unsat_mask`` proves UNSAT — by syntactic contradiction
(same path node asserted with both signs, or a term against its own
ISZERO) or by falsifying a clause ``solver_cache.build_inloop_pool``
compiled from a host-proved must-UNSAT set.  Every such kill is
therefore SUBSUMED by a host verdict: had the lane survived to the
super-round exit, ``filter_feasible``'s memo/subsumption/propagation
tiers would have discarded it before any detector or hook observed it
(parked findings from hook replay are screened against the same UNSAT
path condition and dropped).  Killing it on device produces the same
observable result minus the lift — and exactly like the REVERT prune,
the dying lane's steps/static_pruned/visited planes are folded into
the fused accumulators (a separate ``in-loop kills`` counter rides the
info vector) so counters and coverage stay indistinguishable from a
host ``filter_feasible`` kill.  The device never decides SAT, never
touches the verdict memo, and UNKNOWN lanes ride to the post-round
``decide_batch`` drain unchanged; ``MYTHRIL_TPU_INLOOP_SOLVE=0``
(backend) restores the exact pre-ISSUE-19 loop.
"""

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mythril_tpu.laser.tpu import inloop_solve
from mythril_tpu.laser.tpu import mesh as mesh_lib
from mythril_tpu.laser.tpu.batch import (
    RUNNING,
    REVERTED,
    TRAP,
    CodeBank,
    Env,
    StateBatch,
)
from mythril_tpu.laser.tpu.engine import op_hist_update, step

I32 = jnp.int32

# byte opcode a lane freezes at when REVERT is host-routed (backend
# _ALWAYS_HOST): in the integrated pipeline a reverting lane never
# reaches status REVERTED — it TRAPs AT the REVERT instruction. Direct
# engine runs (host_ops without REVERT) do reach REVERTED; the prune
# mask accepts both encodings.
REVERT_OP = 0xFD


class FusedOut(NamedTuple):
    """Result of one fused super-round dispatch."""

    st: StateBatch
    # i32[7] packed scalars — ONE host fetch decodes all of them:
    # [rounds_done, pruned_lanes, pruned_steps, pruned_static,
    #  n_alive, n_running, inloop_kills]
    info: jnp.ndarray
    # bool[n_codes, code_len] union of PRUNED lanes' visited planes —
    # their coverage must still be harvested (measurement parity with
    # the host path, which would have lifted them before discarding)
    pruned_visited: jnp.ndarray
    # u32[256] retired-opcode histogram (with_stats) or u32[1] dummy
    hist: jnp.ndarray


def prune_mask(cb: CodeBank, st: StateBatch) -> jnp.ndarray:
    """bool[L]: lanes whose lift is provably unobservable this round."""
    at_revert = (st.status == REVERTED) | (
        (st.status == TRAP) & (st.trap_op == REVERT_OP)
    )
    return st.alive & st.outermost & cb.prune_revert & at_revert


def compact_impl(st: StateBatch) -> StateBatch:
    """Permute lanes so the alive frontier is a dense prefix.

    Stable sort on the dead flag: survivors keep their relative order,
    dead lanes (free fork slots) sink to the suffix. Every plane is
    lane-major, so one gather order applies to the whole pytree."""
    order = jnp.argsort(st.alive.astype(I32), descending=True, stable=True)
    return jax.tree_util.tree_map(lambda x: x[order], st)


compact = jax.jit(compact_impl, donate_argnames=("st",))


def _one_round(
    cb: CodeBank,
    env: Env,
    s: StateBatch,
    hist,
    pl,
    ps,
    px,
    pv,
    uk,
    pool,
    steps_per_round: int,
    with_stats: bool,
    with_solve: bool,
):
    """One fused round: step ``steps_per_round`` times, REVERT-prune
    and (with ``with_solve``) in-loop-UNSAT-kill — folding the dying
    lanes' counters into the accumulators either way — then compact.

    Shared verbatim by the single-device megakernel and the shard_map
    mesh body — on a lane-sharded batch every op here is lane-local
    (the clause pool is replicated), so GSPMD/shard_map partition it
    with zero communication."""

    def one_step(_, inner):
        s2, h = inner
        ns = step(cb, env, s2)
        if with_stats:
            h = op_hist_update(cb, s2, ns, h)
        return ns, h

    s, hist = jax.lax.fori_loop(0, steps_per_round, one_step, (s, hist))

    # prune: fold the dying lanes' observable counters into the
    # carry accumulators before the kill — the host merges them so
    # steps/coverage/static-prune accounting matches the lift path
    dead = prune_mask(cb, s)
    # in-loop solve: must-UNSAT forks die here, mid-super-round, with
    # the exact counter/coverage folds of the REVERT prune (module
    # docstring, in-loop-UNSAT soundness). Tracked on its own
    # accumulator so the seam metric (in_loop_unsat_kills) stays
    # separable from static revert pruning.
    if with_solve:
        killed = inloop_solve.unsat_mask(pool, s) & ~dead
    else:
        killed = jnp.zeros_like(dead)
    dying = dead | killed
    pl = pl + jnp.sum(dead.astype(I32))
    uk = uk + jnp.sum(killed.astype(I32))
    ps = ps + jnp.sum(jnp.where(dying, s.steps, 0))
    px = px + jnp.sum(jnp.where(dying, s.static_pruned, 0))
    pv = pv.at[s.code_id].max(dying[:, None] & s.visited)
    # zero the dying lanes' counter planes: the host sums steps/
    # static_pruned over ALL lanes, so a stale copy left in a free
    # lane would double-count against the accumulators above
    s = s._replace(
        alive=s.alive & ~dying,
        steps=jnp.where(dying, 0, s.steps),
        static_pruned=jnp.where(dying, 0, s.static_pruned),
        visited=jnp.where(dying[:, None], False, s.visited),
    )
    s = compact_impl(s)
    return s, hist, pl, ps, px, pv, uk


@partial(
    jax.jit,
    static_argnames=("steps_per_round", "with_stats", "with_solve"),
    donate_argnames=("st",),
)
def _fused_impl(
    cb: CodeBank,
    env: Env,
    st: StateBatch,
    max_rounds,
    pool,
    steps_per_round: int = 512,
    with_stats: bool = False,
    with_solve: bool = False,
) -> FusedOut:
    """The megakernel body. ``max_rounds`` is TRACED (a runtime scalar),
    so the adaptive-K controller never triggers a recompile; only
    ``steps_per_round``/``with_stats``/``with_solve`` specialize the
    kernel. The clause ``pool`` is traced too — solver_cache can refresh
    clauses between dispatches without recompiling."""
    n_codes = cb.code.shape[0]
    W = st.visited.shape[1]

    def cond(carry):
        r, s, _pl, _ps, _px, _pv, _uk, _hist = carry
        # needs_host reduction: RUNNING lanes still make device
        # progress; everything else is halted or frozen at a host op
        return (r < max_rounds) & jnp.any(s.alive & (s.status == RUNNING))

    def body(carry):
        r, s, pl, ps, px, pv, uk, hist = carry
        s, hist, pl, ps, px, pv, uk = _one_round(
            cb, env, s, hist, pl, ps, px, pv, uk, pool,
            steps_per_round=steps_per_round, with_stats=with_stats,
            with_solve=with_solve,
        )
        return r + 1, s, pl, ps, px, pv, uk, hist

    zero = jnp.asarray(0, I32)
    hist0 = jnp.zeros((256 if with_stats else 1,), jnp.uint32)
    pv0 = jnp.zeros((n_codes, W), jnp.bool_)
    r, out, pl, ps, px, pv, uk, hist = jax.lax.while_loop(
        cond, body, (zero, st, zero, zero, zero, pv0, zero, hist0)
    )
    n_alive = jnp.sum(out.alive.astype(I32))
    n_running = jnp.sum((out.alive & (out.status == RUNNING)).astype(I32))
    info = jnp.stack([r, pl, ps, px, n_alive, n_running, uk])
    return FusedOut(st=out, info=info, pruned_visited=pv, hist=hist)


class FusedStats(NamedTuple):
    """Host-side decode of :class:`FusedOut.info`."""

    rounds: int
    pruned_lanes: int
    pruned_steps: int
    pruned_static: int
    n_alive: int
    n_running: int
    inloop_kills: int


def run_fused(
    cb: CodeBank,
    env: Env,
    st: StateBatch,
    max_rounds: int,
    steps_per_round: int = 512,
    with_stats: bool = False,
    with_solve: bool = False,
    pool=None,
) -> FusedOut:
    """Dispatch one fused super-round (up to ``max_rounds`` device
    rounds without a host sync). The caller owns the single host fetch
    of ``out.info`` — nothing here blocks on device results — and the
    ``fused_super_round`` span, which runs from dispatch to that fetch."""
    if pool is None:
        pool = inloop_solve.empty_pool()  # noqa: clause-free pool, sound anywhere
    return _fused_impl(
        cb,
        env,
        st,
        jnp.asarray(int(max_rounds), I32),
        pool,
        steps_per_round=steps_per_round,
        with_stats=with_stats,
        with_solve=bool(with_solve),  # noqa: static python arg, not a tracer
    )


def decode_info(info) -> FusedStats:
    """ONE blocking device->host fetch for all fused-round scalars."""
    import numpy as np

    vals = np.asarray(info)  # noqa: device_loop_purity — host-side decode
    return FusedStats(
        rounds=int(vals[0]),
        pruned_lanes=int(vals[1]),
        pruned_steps=int(vals[2]),
        pruned_static=int(vals[3]),
        n_alive=int(vals[4]),
        n_running=int(vals[5]),
        inloop_kills=int(vals[6]),
    )


# ---------------------------------------------------------------------------
# fused MESH path: the same super-round under shard_map, with on-device
# ICI work-stealing between rounds (docs/MESH.md)
# ---------------------------------------------------------------------------

_AX = "paths"


class MeshFusedStats(NamedTuple):
    """Host-side decode of the fused-MESH info vector
    (i32[9 + n_shards]: eight scalars, the per-shard occupancy block,
    then the in-loop kill count).

    The first six fields mirror :class:`FusedStats`; the steal
    counters, the per-shard frontier occupancy, and the in-loop-UNSAT
    kill count ride the SAME vector, so their accounting costs zero
    extra host syncs (the whole point of folding them into ``info``)."""

    rounds: int
    pruned_lanes: int
    pruned_steps: int
    pruned_static: int
    n_alive: int
    n_running: int
    steal_events: int
    steal_lanes: int
    occupancy: tuple  # per-shard running lanes at loop exit
    inloop_kills: int


def decode_mesh_info(info, n_shards: int) -> MeshFusedStats:
    """ONE blocking device->host fetch for all fused-mesh scalars."""
    import numpy as np

    vals = np.asarray(info)  # noqa: device_loop_purity — host-side decode
    return MeshFusedStats(
        rounds=int(vals[0]),
        pruned_lanes=int(vals[1]),
        pruned_steps=int(vals[2]),
        pruned_static=int(vals[3]),
        n_alive=int(vals[4]),
        n_running=int(vals[5]),
        steal_events=int(vals[6]),
        steal_lanes=int(vals[7]),
        occupancy=tuple(int(v) for v in vals[8 : 8 + n_shards]),
        inloop_kills=int(vals[8 + n_shards]),
    )


@lru_cache(maxsize=None)
def _mesh_kernel(mesh, steps_per_round: int, with_stats: bool, with_solve: bool):
    """Compile the fused super-round for one mesh shape.

    The whole megakernel loop runs INSIDE ``shard_map``: every shard
    owns a contiguous lane block (StateBatch planes sharded on the
    leading axis, CodeBank/env/clause-pool replicated), the round body
    is the exact single-device ``_one_round`` (lane-local, zero
    communication — the in-loop solve reads only the replicated pool
    and the shard's own lanes), and the only collectives are
    deliberate — the psum quiescence check in the loop cond, and the
    steal_plan/steal_apply all-gather + all-to-all between rounds.
    Keyed on the (hashable, cached) Mesh so repeated dispatches reuse
    one executable; ``max_rounds`` stays traced exactly as on the
    single-device path."""
    from jax.experimental.shard_map import shard_map

    n = mesh.devices.size

    def shard_body(cb, env, st, max_rounds, pool):
        n_codes = cb.code.shape[0]
        W = st.visited.shape[1]

        def cond(carry):
            r, s, *_rest = carry
            local = jnp.any(s.alive & (s.status == RUNNING)).astype(I32)
            # quiescence is GLOBAL: a drained shard keeps serving steal
            # collectives until the whole mesh frontier is empty (every
            # shard must iterate in lockstep for the all-to-alls)
            return (r < max_rounds) & (jax.lax.psum(local, _AX) > 0)

        def body(carry):
            r, s, pl, ps, px, pv, uk, hist, sev, sln = carry
            s, hist, pl, ps, px, pv, uk = _one_round(
                cb, env, s, hist, pl, ps, px, pv, uk, pool,
                steps_per_round=steps_per_round, with_stats=with_stats,
                with_solve=with_solve,
            )
            # work-steal between rounds: the plan is derived from one
            # tiny all-gather, identical on every shard, so the cond
            # predicate is mesh-uniform and the all-to-all inside the
            # taken branch executes on all shards or none
            plan = mesh_lib.steal_plan(s, n, axis=_AX)
            spread = jnp.max(plan.occ) - jnp.min(plan.occ)
            do_steal = (plan.moved > 0) & (spread > 1)

            def _steal(s_):
                return compact_impl(mesh_lib.steal_apply(s_, plan, n, axis=_AX))

            s = jax.lax.cond(do_steal, _steal, lambda s_: s_, s)
            sev = sev + do_steal.astype(I32)
            sln = sln + jnp.where(do_steal, plan.moved, 0)
            return r + 1, s, pl, ps, px, pv, uk, hist, sev, sln

        zero = jnp.asarray(0, I32)
        hist0 = jnp.zeros((256 if with_stats else 1,), jnp.uint32)
        pv0 = jnp.zeros((n_codes, W), jnp.bool_)
        r, out, pl, ps, px, pv, uk, hist, sev, sln = jax.lax.while_loop(
            cond,
            body,
            (zero, st, zero, zero, zero, pv0, zero, hist0, zero, zero),
        )

        # fold the per-shard accumulators into mesh-wide replicated
        # outputs; occupancy and the in-loop kill count ride the same
        # info vector (zero extra host syncs for gauges/steal gating)
        running = out.alive & (out.status == RUNNING)
        occ = jax.lax.all_gather(jnp.sum(running.astype(I32)), _AX)
        n_alive = jax.lax.psum(jnp.sum(out.alive.astype(I32)), _AX)
        pl = jax.lax.psum(pl, _AX)
        ps = jax.lax.psum(ps, _AX)
        px = jax.lax.psum(px, _AX)
        uk = jax.lax.psum(uk, _AX)
        pv = jax.lax.psum(pv.astype(jnp.uint32), _AX) > 0
        hist = jax.lax.psum(hist, _AX)
        info = jnp.concatenate(
            [
                jnp.stack([r, pl, ps, px, n_alive, jnp.sum(occ), sev, sln]),
                occ,
                uk[None],
            ]
        )
        return FusedOut(st=out, info=info, pruned_visited=pv, hist=hist)

    sm = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(), P(_AX), P(), P()),
        out_specs=FusedOut(st=P(_AX), info=P(), pruned_visited=P(), hist=P()),
        check_rep=False,
    )
    return jax.jit(sm, donate_argnums=(2,))


def run_fused_mesh(
    mesh,
    cb: CodeBank,
    env: Env,
    st: StateBatch,
    max_rounds: int,
    steps_per_round: int = 512,
    with_stats: bool = False,
    with_solve: bool = False,
    pool=None,
) -> FusedOut:
    """Dispatch one fused MESH super-round (sharded ``st``, replicated
    ``cb``/``env``/``pool``). As on the single-device path, nothing here
    blocks — the caller owns the single ``info`` fetch
    (``decode_mesh_info``)."""
    n = mesh.devices.size
    if st.pc.shape[0] % n != 0:
        raise ValueError(
            f"lane count {st.pc.shape[0]} not divisible by mesh size {n}"
        )
    if pool is None:
        pool = inloop_solve.empty_pool()  # noqa: clause-free pool, sound anywhere
    fn = _mesh_kernel(mesh, steps_per_round, bool(with_stats), bool(with_solve))  # noqa: host-side cache key normalization
    return fn(cb, env, st, jnp.asarray(int(max_rounds), I32), pool)
