#!/usr/bin/env bash
# Repo quality gate (VERDICT r3 #10; reference parity: tox.ini mypy +
# CircleCI black). mypy/black are not installable in this image, so the
# gate is: stdlib byte-compilation of every module, the ast-based lint
# (scripts/lint.py: unused imports, undefined names, mutable defaults,
# swallowed exceptions, whitespace discipline — over mythril_tpu/ AND
# tests/), a pytest collection sanity pass, and the static-pass golden
# fixture tests (fast, no symbolic execution). CPU-only.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== byte-compile =="
python -m compileall -q mythril_tpu tests scripts bench.py __graft_entry__.py

echo "== package hygiene =="
# every Python package directory under mythril_tpu/ must contain at
# least one tracked source file — a dir holding only __pycache__ is a
# stale remnant of a deleted package and shadows future imports. Dirs
# with no .py surface at all (e.g. _build/ native artifacts) are not
# packages and are left alone.
stale=0
while IFS= read -r dir; do
    if [ -n "$(git ls-files "$dir")" ]; then
        continue
    fi
    if [ -d "$dir/__pycache__" ] || compgen -G "$dir/*.py" > /dev/null; then
        echo "stale package (no tracked files): $dir"
        stale=1
    fi
done < <(find mythril_tpu -type d -not -name __pycache__)
[ "$stale" -eq 0 ] || exit 1
echo "package hygiene ok"

echo "== lint =="
python scripts/lint.py

echo "== pytest collection =="
python -m pytest tests/ -q --collect-only > /dev/null
echo "collection ok"

echo "== static-pass golden tests =="
# -k keeps this to the fast fixture/decode tests; the symbolic-execution
# property tests in the same files run with the full suite
python -m pytest tests/analysis/test_static_pass.py \
    tests/analysis/test_taint_pass.py \
    tests/analysis/test_disassembler_truncated.py \
    -q -p no:cacheprovider -k "golden or cache or push or scan"

echo "== solver fast tests =="
# the solver boundary: memo/subsumption/fingerprints, pool cancellation
# hygiene, and the pad-ladder compile bound. Deselect the on-device
# classes (they compile XLA kernels; the full suite runs them) — the
# memo and pool logic here is pure host-side and runs in seconds.
python -m pytest tests/laser/test_solver_cache.py \
    tests/laser/test_solver_fallback.py \
    -q -p no:cacheprovider \
    -k "not on_device and not witness"

echo "== rewrite-pass fast tests =="
# stage-3 rule soundness against the evaluate oracle, interval
# discharge, and memo-key stability — pure host-side, sub-second. The
# host-CDCL-backed equisatisfiability and core-minimization tests run
# with the full suite; -k trims to the oracle/engine half.
python -m pytest tests/laser/test_rewrite_pass.py \
    -q -p no:cacheprovider \
    -k "rule or idempotent or transfer or fingerprint or structural"

echo "== service fast tests =="
# scheduler/cache/api lifecycle with the pipeline stubbed out — no
# symbolic execution; the real multi-tenant integration runs in
# tests/service/test_multitenant.py with the full suite
python -m pytest tests/service/test_cache.py \
    tests/service/test_scheduler.py \
    tests/service/test_api.py \
    -q -p no:cacheprovider

echo "== obs fast tests =="
# metrics registry, tracer, the phase helper, and the service metrics
# endpoint (stubbed lifecycle) — all host-side, sub-second. The golden
# trace-schema test runs a real device pipeline and is deselected here;
# it runs with the full suite.
python -m pytest tests/obs/ \
    -q -p no:cacheprovider \
    -k "not golden and not injected_fault"

echo "== robustness fast tests =="
# fault harness parsing/determinism, retry ladder + breaker transitions,
# checkpoint journal, and the scheduler crash-isolation/quarantine unit
# tests — all host-side stubs, no symbolic execution. The full
# fault-matrix property test (every seam x every fault kind through the
# real pipeline) runs with the full suite; -k trims to the fast half.
python -m pytest tests/robustness/ \
    -q -p no:cacheprovider \
    -k "not matrix and not slow"

echo "== fleet fast tests =="
# fleet tier, no devices by construction (the fleet_boundary lint rule
# keeps jax out of gateway/store): hash-ring routing, durable-store
# crash recovery, QoS tuning, gateway death/re-route, and the 2-worker
# in-proc smoke — dup bytecode warm-hits across workers through the
# shared store and a watch stream delivers an issue event before the
# job completes. Subprocess fleet integration (real `myth serve`
# workers) runs with bench.py --fleet, not here.
python -m pytest tests/fleet/ \
    -q -p no:cacheprovider \
    -k "not subprocess and not slow"

echo "== megakernel smoke =="
# fused device-loop smoke: one tiny-lane compile of the megakernel plus
# the compaction/prune unit checks (CPU jit, seconds). The fused-vs-
# legacy equivalence and the full S2 compaction property test run with
# the full suite; -k trims to the fast half.
python -m pytest tests/laser/test_megakernel.py \
    -q -p no:cacheprovider \
    -k "smoke or compact_basic or prune_mask"

echo "== virtual-mesh smoke =="
# fused MESH path on the 8-virtual-CPU-device mesh (conftest supplies
# the devices), fused tier forced on: the steal plan/apply invariants
# through a real shard_map all-to-all, one skewed-fork run of the fused
# mesh megakernel (ICI steal fires in-loop), and the tier policy table.
# The mesh-vs-single-device equivalence property tests run with the
# full suite; -k trims to the steal/policy half.
MYTHRIL_TPU_MESH=on python -m pytest tests/laser/test_mesh_fused.py \
    -q -p no:cacheprovider \
    -k "steal or tier or planned"

echo "== in-loop solve fast tests =="
# the in-loop propagation kernel on a tiny CNF: R1/R3 contradiction
# masks, clause-pool unit propagation, the solver_cache pool round-trip
# (note_path_literal -> build_inloop_pool), and a one-lane fused run
# with with_solve on. Pure CPU jit, seconds. The ON/OFF equivalence
# property tests over the bench contracts run with the full suite.
python -m pytest tests/laser/test_inloop_solve.py \
    -q -p no:cacheprovider \
    -k "not equivalence and not mesh"

echo "ALL CHECKS PASSED"
