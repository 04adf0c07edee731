"""Batched bit-blasted tensor solver: frontier-wide feasibility on device.

This is the SURVEY §2.1 ★ core target. The reference runs one Z3 check per
forked state (mythril/laser/ethereum/state/constraints.py:41, called from
svm.py:254); here the whole frontier's path conditions are bit-blasted to
CNF instances (sharing the Blaster gate layer with the host exact solver,
smt/solver/bitblast.py), padded into tensors, and decided in ONE device
call:

  phase 1 — batched boolean constraint propagation: three-valued unit
    propagation to fixpoint across all instances in lockstep. A conflict
    is a sound UNSAT proof (no decisions were made); all-clauses-satisfied
    is a sound SAT witness. EVM path conditions are dominated by
    equality-with-constant conjuncts (function selectors, jump guards), so
    propagation alone settles most instances.
  phase 2 — multi-restart WalkSAT on whatever propagation left open:
    random parallel restarts per instance, flipping variables of random
    unsatisfied clauses. Any all-clauses-satisfied assignment is a sound
    SAT witness (the CNF is Tseitin-equisatisfiable with the formula).

Instances that stay open after the flip budget return UNKNOWN and fall
back to the host incremental CDCL core (smt/solver/incremental.py). Hard
instances (wide multipliers, deep store chains) are rejected during
compilation by gate-count caps *before* any expensive blasting happens —
the early-abort keeps per-instance compile cost in the milliseconds.

Everything here is static-shaped for XLA: instance tensors are padded to
power-of-two buckets (vars/clauses/batch) so recompiles are rare; the
search itself is lax.while_loop'd scalar-free vector work that maps onto
the VPU. Clause width is fixed at 3 (the Blaster's gate layer emits only
1..3-literal clauses), so the clause matrix is [I, C, 3] int32 in HBM.

A third, even cheaper propagation tier lives INSIDE the fused round
loop (laser/tpu/inloop_solve.py): where this module bit-blasts full
formulas post-super-round (the shared prefix cached by ``_BlastTrie``),
the in-loop kernel works at WORD granularity over clauses the solver
cache compiled from already-proved UNSAT sets — phase-1-style unit
propagation only, no search, so a freshly forked must-UNSAT lane dies
between rounds without ending the super-round or reaching this module
at all. Lanes it cannot settle arrive here unchanged.
"""

import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np

from mythril_tpu import obs
from mythril_tpu.obs import catalog as _cat
from mythril_tpu.smt import terms
from mythril_tpu.smt.solver import pysat
from mythril_tpu.smt.solver.bitblast import Blaster, BlastError
from mythril_tpu.smt.solver.preprocess import eliminate_theories
from mythril_tpu.smt.terms import Term

log = logging.getLogger(__name__)

SAT = pysat.SAT
UNSAT = pysat.UNSAT
UNKNOWN = pysat.UNKNOWN

# compile-time caps: instances larger than this go to the host CDCL instead.
# Batches are padded to a SMALL FIXED LADDER of (vars, clauses) buckets —
# canonical shapes mean a bounded number of kernel compiles for the process
# lifetime (first XLA compile is tens of seconds; recompiling per frontier
# shape would burn the analysis time budget), while tiny instances stop
# paying full-size kernel work. Tests shrink these knobs.
MAX_VARS = 4096
MAX_CLAUSES = 1 << 14
MAX_BATCH = 64  # larger frontiers are chunked

# the (vars, clauses) pad ladder, as right-shifts of the current caps:
# three diagonal steps (caps/16, caps/4, caps). Derived lazily from
# MAX_VARS/MAX_CLAUSES so test-shrunk caps get a proportionally shrunk
# ladder. The batch axis has its own two-step ladder below.
_LADDER_SHIFTS = (4, 2, 0)
_BATCH_LADDER = (8, MAX_BATCH)


def shape_ladder():
    """Ascending [(pad_vars, pad_clauses)] buckets under the current caps."""
    out = []
    for shift in _LADDER_SHIFTS:
        step = (max(16, MAX_VARS >> shift), max(64, MAX_CLAUSES >> shift))
        if not out or step != out[-1]:
            out.append(step)
    return out


# (I, V, C, flips) shapes this process has dispatched — each is one jit
# specialization of the solve kernel. Bounded by construction:
# |_BATCH_LADDER| x |shape_ladder()| x |distinct flips| (tests assert it).
_compiled_shapes: set = set()

_jax = None
_jnp = None


def _ensure_jax():
    global _jax, _jnp
    if _jax is None:
        import jax
        import jax.numpy as jnp

        _jax, _jnp = jax, jnp
    return _jax, _jnp


class CapExceeded(Exception):
    """Instance outgrew the device caps during blasting (early abort)."""


class _CappedRecorder:
    """PySat-shaped sink that records CNF instead of solving, aborting as
    soon as the instance exceeds the device size caps."""

    __slots__ = ("nvars", "clauses", "max_vars", "max_clauses")

    def __init__(self, max_vars: int = MAX_VARS, max_clauses: int = MAX_CLAUSES):
        self.nvars = 0
        self.clauses: List[Tuple[int, ...]] = []
        self.max_vars = max_vars
        self.max_clauses = max_clauses

    def new_var(self) -> int:
        self.nvars += 1
        if self.nvars > self.max_vars:
            raise CapExceeded("vars")
        return self.nvars

    def add_clause(self, lits) -> None:
        self.clauses.append(tuple(lits))
        if len(self.clauses) > self.max_clauses:
            raise CapExceeded("clauses")


class CNFInstance:
    """One compiled path condition."""

    __slots__ = ("clause_arr", "nvars", "inputs", "trivial", "var_bits", "bool_vars")

    def __init__(
        self,
        clauses,
        nvars,
        inputs=(),
        trivial: Optional[int] = None,
        var_bits=None,
        bool_vars=None,
    ):
        # pre-packed [n, 3] literal matrix: _pack_batch slice-assigns it
        # instead of looping Python-side per literal on the frontier path
        if isinstance(clauses, np.ndarray):
            arr = clauses
        else:
            arr = np.zeros((len(clauses), 3), dtype=np.int32)
            for ci, cl in enumerate(clauses):
                arr[ci, : len(cl)] = cl
        self.clause_arr = arr
        self.nvars = nvars
        self.inputs = inputs  # SAT vars of the formula's free symbols
        self.trivial = trivial  # SAT/UNSAT decided at compile time, or None
        # (name, size) -> LSB-first bit literals / name -> literal: the
        # bridge between this instance's private var numbering and
        # named-symbol models (warm starts in, witnesses out). CNF var
        # numbers do NOT transfer between instances; models do.
        self.var_bits = var_bits or {}
        self.bool_vars = bool_vars or {}


def compile_cnf(
    assertions: Sequence[Term],
    max_vars: int = MAX_VARS,
    max_clauses: int = MAX_CLAUSES,
) -> Optional[CNFInstance]:
    """Blast one constraint set to a CNF instance; None if it exceeds the
    device caps or contains un-blastable structure."""
    if any(t is terms.FALSE for t in assertions):
        return CNFInstance([], 0, trivial=UNSAT)
    concrete = [t for t in assertions if t is not terms.TRUE]
    if not concrete:
        return CNFInstance([], 0, trivial=SAT)
    rec = _CappedRecorder(max_vars, max_clauses)
    blaster = Blaster(rec)
    try:
        rewritten, _info = eliminate_theories(list(concrete))
        for t in rewritten:
            blaster.assert_formula(t)
    except (CapExceeded, BlastError):
        return None
    inputs = []
    for bits in blaster.var_bits.values():
        inputs.extend(abs(b) for b in bits)
    for lit in blaster.bool_vars.values():
        inputs.append(abs(lit))
    return CNFInstance(
        rec.clauses,
        rec.nvars,
        tuple(inputs),
        var_bits=dict(blaster.var_bits),
        bool_vars=dict(blaster.bool_vars),
    )


def _shrink_dict(d: dict, n: int) -> None:
    # every cache insert during blasting is insert-once (never an
    # overwrite), so the last len(d)-n insertion-ordered keys are exactly
    # the entries added past the savepoint
    while len(d) > n:
        d.popitem()


class _BlastTrie:
    """Shared-prefix incremental blasting for one batch of constraint
    sets.

    Sibling lanes extend their parent's constraint list append-only, so
    a frontier batch re-blasts the same deep prefix once per set —
    measured r6, compile_cnf was ~100% of the device-solve wall time
    (the XLA kernel itself is microseconds). Here the batch is sorted so
    shared prefixes are adjacent, one Blaster/TheoryEliminator pair is
    kept warm, and moving between consecutive sets rolls the state back
    to the common prefix instead of starting over: total gate work is
    the size of the batch's prefix TRIE, not the sum of set sizes.

    Rollback is trail-free: all blaster/eliminator caches are
    insert-once dicts (restored by popping down to the saved length —
    python dicts are insertion-ordered), the clause/side-condition lists
    truncate, and cached word literal-lists are never mutated in place
    so sharing them across savepoints is safe. Asserting a term may
    append Ackermann side conditions mid-stream rather than at the end
    of the set the way eliminate_theories does; the clause set is the
    same, only gate numbering differs (instance numbering is private —
    models travel by symbol name, see CNFInstance.var_bits)."""

    def __init__(self, max_vars: int, max_clauses: int):
        from mythril_tpu.smt.solver.preprocess import TheoryEliminator

        self.rec = _CappedRecorder(max_vars, max_clauses)
        self.blaster = Blaster(self.rec)
        self.elim = TheoryEliminator()
        self._sc_done = 0  # side conditions already asserted

    def savepoint(self):
        b, e = self.blaster, self.elim
        return (
            self.rec.nvars,
            len(self.rec.clauses),
            len(b.gate_cache),
            len(b.word_cache),
            len(b.bool_cache),
            len(b.div_cache),
            len(b.var_bits),
            len(b.bool_vars),
            len(e.memo),
            len(e.sel_vars),
            len(e.app_vars),
            len(e.side_conditions),
            e._fresh,
            self._sc_done,
            {k: len(v) for k, v in e.info.arrays.items()},
            {k: len(v) for k, v in e.info.funcs.items()},
        )

    def rollback(self, sp) -> None:
        b, e = self.blaster, self.elim
        (
            self.rec.nvars,
            n_clauses,
            n_gate,
            n_word,
            n_bool,
            n_div,
            n_vbits,
            n_bvars,
            n_memo,
            n_sel,
            n_app,
            n_sc,
            e._fresh,
            self._sc_done,
            arr_lens,
            fn_lens,
        ) = sp
        del self.rec.clauses[n_clauses:]
        _shrink_dict(b.gate_cache, n_gate)
        _shrink_dict(b.word_cache, n_word)
        _shrink_dict(b.bool_cache, n_bool)
        _shrink_dict(b.div_cache, n_div)
        _shrink_dict(b.var_bits, n_vbits)
        _shrink_dict(b.bool_vars, n_bvars)
        _shrink_dict(e.memo, n_memo)
        _shrink_dict(e.sel_vars, n_sel)
        _shrink_dict(e.app_vars, n_app)
        del e.side_conditions[n_sc:]
        _shrink_dict(e.info.arrays, len(arr_lens))
        for k, n in arr_lens.items():
            del e.info.arrays[k][n:]
        _shrink_dict(e.info.funcs, len(fn_lens))
        for k, n in fn_lens.items():
            del e.info.funcs[k][n:]

    def push(self, t: Term) -> None:
        """Rewrite + assert one more term of the current set, plus any
        Ackermann side conditions its rewrite produced."""
        self.blaster.assert_formula(self.elim.rewrite(t))
        sc = self.elim.side_conditions
        while self._sc_done < len(sc):
            cond = sc[self._sc_done]
            self._sc_done += 1
            self.blaster.assert_formula(cond)

    def snapshot_instance(self) -> CNFInstance:
        b = self.blaster
        clauses = self.rec.clauses
        if clauses:
            arr = np.array(
                [cl + (0,) * (3 - len(cl)) for cl in clauses],
                dtype=np.int32,
            )
        else:
            arr = np.zeros((0, 3), dtype=np.int32)
        inputs = []
        for bits in b.var_bits.values():
            inputs.extend(abs(x) for x in bits)
        for lit in b.bool_vars.values():
            inputs.append(abs(lit))
        return CNFInstance(
            arr,
            self.rec.nvars,
            tuple(inputs),
            var_bits=dict(b.var_bits),
            bool_vars=dict(b.bool_vars),
        )


def compile_cnf_batch(
    constraint_sets: Sequence[Sequence[Term]],
    max_vars: int = MAX_VARS,
    max_clauses: int = MAX_CLAUSES,
) -> List[Optional[CNFInstance]]:
    """Blast a batch of constraint sets with shared-prefix reuse (see
    _BlastTrie). Per-set results match compile_cnf: a CNFInstance
    (possibly trivial), or None past the caps / on un-blastable
    structure."""
    out: List[Optional[CNFInstance]] = [None] * len(constraint_sets)
    keyed = []
    for i, cs in enumerate(constraint_sets):
        if any(t is terms.FALSE for t in cs):
            out[i] = CNFInstance([], 0, trivial=UNSAT)
            continue
        concrete = [t for t in cs if t is not terms.TRUE]
        if not concrete:
            out[i] = CNFInstance([], 0, trivial=SAT)
            continue
        keyed.append((tuple(t.uid for t in concrete), i, concrete))
    if not keyed:
        return out
    keyed.sort(key=lambda kic: kic[0])
    trie = _BlastTrie(max_vars, max_clauses)
    saves = [trie.savepoint()]  # saves[d] = state with d terms asserted
    path: Tuple[int, ...] = ()
    failed: Optional[Tuple[int, ...]] = None
    for key, i, concrete in keyed:
        # a prefix that blew the caps (or hit un-blastable structure)
        # fails identically for every extension — sorted order puts them
        # right here, so skip without re-blasting
        if failed is not None and key[: len(failed)] == failed:
            continue
        k = 0
        m = min(len(path), len(key))
        while k < m and path[k] == key[k]:
            k += 1
        trie.rollback(saves[k])
        del saves[k + 1 :]
        path = key[:k]
        ok = True
        for t in concrete[k:]:
            try:
                trie.push(t)
            except (CapExceeded, BlastError):
                # partial writes past the last savepoint: discard them
                trie.rollback(saves[-1])
                failed = key[: len(saves)]
                path = key[: len(saves) - 1]
                ok = False
                break
            saves.append(trie.savepoint())
        if ok:
            path = key
            out[i] = trie.snapshot_instance()
    return out


def _pow2(n: int, lo: int = 16, ladder=None) -> int:
    """Next padded size. With a ``ladder`` the growth is CLAMPED to the
    fixed bucket steps (bounded jit specializations) instead of free
    power-of-two growth; values beyond the last step return it."""
    if ladder is not None:
        for step in ladder:
            if n <= step:
                return step
        return ladder[-1]
    v = lo
    while v < n:
        v <<= 1
    return v


def _select_bucket(need_vars: int, need_clauses: int):
    """Smallest ladder bucket fitting the instance — promoted to an
    ALREADY-COMPILED larger bucket when one exists (padding waste is
    microseconds; an extra XLA compile is tens of seconds)."""
    ladder = shape_ladder()
    fit = None
    for step in ladder:
        if need_vars <= step[0] and need_clauses <= step[1]:
            fit = step
            break
    if fit is None:
        fit = (max(ladder[-1][0], need_vars), max(ladder[-1][1], need_clauses))
    compiled = {(v, c) for (_i, v, c, _f) in _compiled_shapes}
    if fit not in compiled:
        for step in ladder:
            if step in compiled and step[0] >= fit[0] and step[1] >= fit[1]:
                return step
    return fit


def _pack_batch(instances: List[CNFInstance], pad_vars: int, pad_clauses: int):
    """Pad live instances into canonical [I, C, 3] clause tensors.

    On accelerator backends the batch axis pads all the way to
    MAX_BATCH: each power-of-two bucket is a separate XLA compile of the
    solve kernel, paid inline by the first solve that meets it, while
    the padded dead instances only add device work of a fixed shape.
    Whether that trade pays on the chip is not measured yet.
    """
    C = pad_clauses
    V = pad_vars
    from mythril_tpu.laser.tpu import transfer

    if transfer.monomorphic():
        I = _pow2(len(instances), lo=MAX_BATCH)
    else:
        I = _pow2(len(instances), ladder=_BATCH_LADDER)
    lits = np.zeros((I, C, 3), dtype=np.int32)
    nvars = np.zeros((I,), dtype=np.int32)
    is_input = np.zeros((I, V), dtype=bool)
    for k, inst in enumerate(instances):
        nvars[k] = inst.nvars
        if inst.inputs:
            is_input[k, np.asarray(inst.inputs, dtype=np.int64) - 1] = True
        lits[k, : inst.clause_arr.shape[0]] = inst.clause_arr
    return lits, nvars, is_input, V


def _solve_kernel(lits, key, nvars, is_input, warm, pad_vars: int, flips: int):
    """lits: [I, C, 3] int32 (0-padded); key: PRNG key; nvars: [I] real var
    counts (decisions never touch padding vars); is_input: [I, V] mask of
    the formula's free-symbol bits — decided first so the Tseitin circuit
    evaluates by propagation instead of conflicting on random gate guesses;
    warm: [I, V] int8 preferred decision phases from a parent path's
    cached model (0 = no preference). Warm phases bias ONLY the phase-2
    decision polarity — phase 1 must stay decision-free or its conflict
    proofs stop being sound UNSAT — and only for the first quarter of
    the flip budget, so a stale parent model cannot pin the search in a
    deterministic conflict loop (later decisions revert to random).

    Returns (status[I], assign[I, V])."""
    jax, jnp = _ensure_jax()
    lax = jax.lax
    I, C, _ = lits.shape
    V = pad_vars

    var = jnp.abs(lits) - 1  # [I,C,3]; -1 for padding
    vidx = jnp.clip(var, 0, V - 1)
    sign = lits > 0
    real = lits != 0  # literal exists
    real_clause = real.any(-1)  # [I,C]
    iidx = jnp.arange(I)[:, None, None]

    def lit_values(val):
        v = val[iidx, vidx]  # [I,C,3]
        return jnp.where(real, jnp.where(sign, v, -v), 0)

    # ---- phase 1: three-valued unit propagation ----
    def prop_body(state):
        val, changed, conflict = state
        lit_val = lit_values(val)
        c_sat = (lit_val == 1).any(-1)
        n_unknown = ((lit_val == 0) & real).sum(-1)
        dead = real_clause & ~c_sat & (n_unknown == 0)
        new_conflict = dead.any(-1)  # [I]
        unit = real_clause & ~c_sat & (n_unknown == 1)  # [I,C]
        # index of the unknown literal in each unit clause
        unk_pos = jnp.argmax((lit_val == 0) & real, axis=-1)  # [I,C]
        u_lit = jnp.take_along_axis(lits, unk_pos[..., None], axis=-1)[..., 0]
        u_var = jnp.clip(jnp.abs(u_lit) - 1, 0, V - 1)
        u_val = jnp.where(u_lit > 0, 1, -1).astype(jnp.int8)
        # scatter forced values (sentinel -2 = no force); if two clauses force
        # opposite values in one pass, max() picks one and the loser's clause
        # turns into a conflict next round
        upd = jnp.full((I, V), -2, dtype=jnp.int8)
        upd = upd.at[jnp.arange(I)[:, None], u_var].max(
            jnp.where(unit, u_val, jnp.int8(-2)), mode="drop"
        )
        force = upd > jnp.int8(-2)
        new_val = jnp.where((val == 0) & force, upd, val)
        new_changed = (new_val != val).any()
        return new_val, new_changed, conflict | new_conflict

    def prop_cond(state):
        _, changed, conflict = state
        return changed & ~conflict.all()

    val0 = jnp.zeros((I, V), dtype=jnp.int8)
    val, _, conflict = lax.while_loop(
        prop_cond, prop_body, (val0, jnp.bool_(True), jnp.zeros(I, dtype=bool))
    )

    lit_val = lit_values(val)
    c_sat = (lit_val == 1).any(-1)
    all_sat = (c_sat | ~real_clause).all(-1)  # [I]
    status0 = jnp.where(conflict, UNSAT, jnp.where(all_sat, SAT, UNKNOWN)).astype(
        jnp.int32
    )

    # ---- phase 2: vectorized random-order DPLL (no backtracking) ----
    # Tseitin CNF propagates extremely well: once the free inputs of the
    # circuit are decided, every gate output is forced by unit propagation.
    # So the search loop alternates one propagation sweep with one random
    # decision (only when propagation is quiescent), and on conflict simply
    # restarts the instance from the phase-1 fixpoint with fresh randomness.
    # Conflicts under decisions prove nothing — only phase 1 yields UNSAT.
    fixed_val = val  # decision-free fixpoint: sound restart point
    varmask = jnp.arange(V)[None, :] < nvars[:, None]  # [I,V]

    # seed the search start from the warm model directly (assignment, not
    # just decision bias): an exact parent witness propagates to all-SAT
    # with zero decisions, while a stale one conflicts and restarts from
    # the sound fixpoint above. status0 is already fixed, so this cannot
    # affect the decision-free UNSAT/SAT verdicts.
    val = jnp.where(
        (val == jnp.int8(0)) & varmask & (warm != jnp.int8(0)), warm, val
    )

    def search_body(carry):
        val, key, status, steps = carry
        lit_val = lit_values(val)
        c_sat = (lit_val == 1).any(-1)
        n_unknown = ((lit_val == 0) & real).sum(-1)
        dead = (real_clause & ~c_sat & (n_unknown == 0)).any(-1)  # [I]
        allsat = (c_sat | ~real_clause).all(-1)
        status = jnp.where((status == UNKNOWN) & allsat & ~dead, SAT, status)
        # unit-force pass (same scatter scheme as phase 1)
        unit = real_clause & ~c_sat & (n_unknown == 1)
        unk_pos = jnp.argmax((lit_val == 0) & real, axis=-1)
        u_lit = jnp.take_along_axis(lits, unk_pos[..., None], axis=-1)[..., 0]
        u_var = jnp.clip(jnp.abs(u_lit) - 1, 0, V - 1)
        u_val = jnp.where(u_lit > 0, 1, -1).astype(jnp.int8)
        upd = jnp.full((I, V), -2, dtype=jnp.int8)
        upd = upd.at[jnp.arange(I)[:, None], u_var].max(
            jnp.where(unit, u_val, jnp.int8(-2)), mode="drop"
        )
        force = upd > jnp.int8(-2)
        val2 = jnp.where((val == 0) & force, upd, val)
        changed = (val2 != val).any(-1)  # [I]
        # quiescent + open + consistent -> decide the LOWEST unassigned
        # var, preferring free-symbol input bits over gate vars, with a
        # random phase. Bit-blasted words allocate LSB-first, so in-order
        # decisions track carry/borrow ripple instead of guessing high
        # bits before their carries exist (random order restarts forever
        # on adder chains); the random phase still de-correlates restarts.
        key, k_p = jax.random.split(key)
        cand = (val2 == 0) & varmask
        cand_in = cand & is_input
        use_in = cand_in.any(-1, keepdims=True)
        pool = jnp.where(use_in, cand_in, cand)
        prio = -jnp.arange(V, dtype=jnp.float32)[None, :]
        dvar = jnp.argmax(jnp.where(pool, prio, -jnp.inf), axis=-1)
        has_cand = cand.any(-1)
        need_decide = (status == UNKNOWN) & ~dead & ~changed & has_cand
        dphase = jnp.where(
            jax.random.bernoulli(k_p, 0.5, (I,)), jnp.int8(1), jnp.int8(-1)
        )
        wcol = warm[jnp.arange(I), dvar]  # [I] int8, 0 = no hint
        dphase = jnp.where((wcol != 0) & (steps < flips // 4), wcol, dphase)
        cur = val2[jnp.arange(I), dvar]
        val3 = val2.at[jnp.arange(I), dvar].set(
            jnp.where(need_decide, dphase, cur)
        )
        # conflict under decisions -> restart from the sound fixpoint
        restart = dead & (status == UNKNOWN)
        val4 = jnp.where(restart[:, None], fixed_val, val3)
        return val4, key, status, steps + 1

    def search_cond(carry):
        _, _, status, steps = carry
        return (steps < flips) & (status == UNKNOWN).any()

    if flips > 0:
        val, _, status, _ = lax.while_loop(
            search_cond,
            search_body,
            (val, key, status0, jnp.zeros((), jnp.int32)),
        )
    else:
        status = status0
    best_assign = val > 0
    return status, best_assign


_jitted_kernel = None


def _get_kernel():
    global _jitted_kernel
    jax, _ = _ensure_jax()
    if _jitted_kernel is None:
        _jitted_kernel = jax.jit(_solve_kernel, static_argnums=(5, 6))
    return _jitted_kernel


_seed_counter = [0]



def _warm_plane(chunk, models, I: int, V: int):
    """[I, V] int8 decision-phase hints from named-symbol models (0 =
    no hint). Model keys are ("bv", name, size) -> int and
    ("bool", name) -> bool; each instance re-projects them onto its own
    private CNF var numbering via the retained blaster maps."""
    warm = np.zeros((I, V), dtype=np.int8)
    for k, (inst, model) in enumerate(zip(chunk, models)):
        if not model:
            continue
        for (name, size), bits in inst.var_bits.items():
            val = model.get(("bv", name, size))
            if val is None:
                continue
            for bi, lit in enumerate(bits):
                v = abs(lit) - 1
                if 0 <= v < V:
                    bit_set = ((val >> bi) & 1) == 1
                    warm[k, v] = 1 if bit_set == (lit > 0) else -1
        for name, lit in inst.bool_vars.items():
            bval = model.get(("bool", name))
            v = abs(lit) - 1
            if bval is not None and 0 <= v < V:
                warm[k, v] = 1 if bool(bval) == (lit > 0) else -1
    return warm


def _extract_model(inst: CNFInstance, assign_row) -> dict:
    """Named-symbol model from a verified SAT assignment row."""
    model: dict = {}
    for (name, size), bits in inst.var_bits.items():
        val = 0
        for bi, lit in enumerate(bits):
            v = abs(lit) - 1
            if 0 <= v < len(assign_row) and bool(assign_row[v]) == (lit > 0):
                val |= 1 << bi
        model[("bv", name, size)] = val
    for name, lit in inst.bool_vars.items():
        v = abs(lit) - 1
        if 0 <= v < len(assign_row):
            model[("bool", name)] = bool(assign_row[v]) == (lit > 0)
    return model


def check_batch(
    constraint_sets: Sequence[Sequence[Term]],
    flips: Optional[int] = None,
    max_vars: int = MAX_VARS,
    max_clauses: int = MAX_CLAUSES,
    models: Optional[Sequence[Optional[dict]]] = None,
    return_models: bool = False,
):
    """Decide a batch of path conditions on device.

    Returns one of pysat.SAT / pysat.UNSAT / pysat.UNKNOWN per input set.
    SAT and UNSAT results are sound (see module docstring); UNKNOWN means
    the caller should fall back to the host CDCL core.

    ``models`` optionally supplies per-set named-symbol warm-start hints
    (see _warm_plane); ``return_models=True`` additionally returns the
    named-symbol witness for each SAT verdict:
    ``(codes, [model-or-None])``. Instances are grouped onto the fixed
    (vars, clauses) pad ladder so jit specializations stay bounded.
    """
    from mythril_tpu.robustness import faults

    faults.fire(faults.SOLVER_BATCH, context="check_batch")
    n = len(constraint_sets)
    results = [UNKNOWN] * n
    models_out: List[Optional[dict]] = [None] * n
    max_vars = min(max_vars, MAX_VARS)
    max_clauses = min(max_clauses, MAX_CLAUSES)
    live_idx = []
    live_instances = []
    # bitblast/CNF compile cost attributed separately from the kernel
    # dispatch (obs: the two dominate different workloads)
    with obs.TRACER.span("cnf_compile", tid="solve", n=n):
        compiled = list(
            compile_cnf_batch(constraint_sets, max_vars, max_clauses)
        )
    cnf_vars = 0
    cnf_clauses = 0
    for i, inst in enumerate(compiled):
        if inst is None:
            continue
        if inst.trivial is not None:
            results[i] = inst.trivial
            continue
        cnf_vars += int(inst.nvars)
        cnf_clauses += int(inst.clause_arr.shape[0])
        live_idx.append(i)
        live_instances.append(inst)
    # real blast volume: what the rewrite pass is measured against
    # (MYTHRIL_TPU_REWRITE=0 control; docs/REWRITE_PASS.md)
    if cnf_vars:
        _cat.CNF_VARS_TOTAL.inc(cnf_vars)
        _cat.CNF_CLAUSES_TOTAL.inc(cnf_clauses)
    if not live_instances:
        return (results, models_out) if return_models else results

    jax, jnp = _ensure_jax()
    kernel = _get_kernel()
    if flips is None:
        flips = min(2 * MAX_VARS + 512, 4096)

    # group by pad bucket (homogeneous chunks), then chunk by MAX_BATCH
    groups: dict = {}
    for j, inst in enumerate(live_instances):
        bucket = _select_bucket(inst.nvars, inst.clause_arr.shape[0])
        groups.setdefault(bucket, []).append(j)
    for (V_b, C_b), members in sorted(groups.items()):
        for lo in range(0, len(members), MAX_BATCH):
            chunk_js = members[lo : lo + MAX_BATCH]
            chunk = [live_instances[j] for j in chunk_js]
            lits, nvars, is_input, V = _pack_batch(chunk, V_b, C_b)
            I = lits.shape[0]
            chunk_models = [
                models[live_idx[j]] if models is not None else None
                for j in chunk_js
            ]
            warm = _warm_plane(chunk, chunk_models, I, V)
            _seed_counter[0] += 1
            key = jax.random.PRNGKey(_seed_counter[0])
            # one upload: the operand arrays ride a single buffer (one
            # host->device transfer instead of four)
            from mythril_tpu.laser.tpu import transfer

            d_lits, d_nvars, d_input, d_warm = transfer.upload_segments(
                [lits, nvars, is_input, warm]
            )
            _compiled_shapes.add((I, V, C_b, flips))
            status, assign = kernel(
                d_lits, key, d_nvars, d_input, d_warm, V, flips
            )
            status = np.asarray(status)
            assign_np = np.asarray(assign) if return_models else None
            for k, j in enumerate(chunk_js):
                code = int(status[k])
                results[live_idx[j]] = code
                if return_models and code == SAT:
                    models_out[live_idx[j]] = _extract_model(
                        live_instances[j], assign_np[k]
                    )
    return (results, models_out) if return_models else results


def feasibility_batch(
    constraint_sets,
    models: Optional[Sequence[Optional[dict]]] = None,
    return_models: bool = False,
    **kw,
) -> List[Optional[bool]]:
    """Frontier filtering helper: True (feasible) / False (infeasible) /
    None (undecided on device; check on host). With
    ``return_models=True`` returns ``(verdicts, witness models)``."""
    res = check_batch(
        constraint_sets, models=models, return_models=return_models, **kw
    )
    codes, witness = res if return_models else (res, None)
    out = []
    for code in codes:
        if code == SAT:
            out.append(True)
        elif code == UNSAT:
            out.append(False)
        else:
            out.append(None)
    return (out, witness) if return_models else out
