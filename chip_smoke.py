#!/usr/bin/env python3
"""Smoke test of `myth analyze --strategy tpu-batch` on a TPU.

Runs the deployment this repository is built for, the BASELINE.json
north-star configuration: the BECToken batchTransfer reproduction
(bench_contracts/bectoken.asm, wrapped in creation code) at three
transactions with a 120 s execution budget, through the same CLI entry a
user calls, with the CLI's default batch configuration. The device
kernels are compiled first (backend.warmup_device), so the analysis
cannot drain on the host before the device is ready. The host engine
analyzes the same contract at the same depth as the reference.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the fused mesh tier over four
                                       # chips against MYTHRIL_TPU_MESH=off

Every phase prints one JSON line. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
when every check held on a TPU. Otherwise it carries ``"ok": false`` and
the names of the failed checks, and the exit code is nonzero: on the
CPU (``JAX_PLATFORMS=cpu``) the script runs every phase as a rehearsal
and then fails, and run outside the repository it fails at import.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import time

BUDGET_S = 120
TX_COUNT = 3
CONTRACT = "bectoken.asm"


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def creation_hex(asm_name: str) -> str:
    """bench_contracts/<asm_name> wrapped in CODECOPY/RETURN deploy code."""
    from mythril_tpu.disassembler.asm import assemble

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "bench_contracts", asm_name)) as fh:
        runtime = assemble(fh.read())
    n = len(runtime)
    deploy = assemble(
        f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\n"
        f"PUSH2 {n}\nPUSH1 0x00\nRETURN\ncode:"
    )
    return deploy.hex() + runtime.hex()


def analyze(code: str, strategy: str):
    """One `myth analyze` run in this process. Returns (exit code, SWC
    ids, wall seconds, the run's SymExecWrapper or None)."""
    from mythril_tpu.core import mythril_analyzer
    from mythril_tpu.interfaces import cli

    made = []

    class Recorded(mythril_analyzer.SymExecWrapper):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            made.append(self)

    argv = [
        "analyze", "-c", code, "--no-onchain-data", "--strategy", strategy,
        "-t", str(TX_COUNT), "--execution-timeout", str(BUDGET_S), "-o", "json",
    ]
    out = io.StringIO()
    saved = mythril_analyzer.SymExecWrapper
    mythril_analyzer.SymExecWrapper = Recorded
    t0 = time.monotonic()
    try:
        with contextlib.redirect_stdout(out):
            cli.main(argv)
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    finally:
        mythril_analyzer.SymExecWrapper = saved
    wall = time.monotonic() - t0
    try:
        issues = json.loads(out.getvalue())["issues"]
    except (ValueError, KeyError):
        issues = []
    swcs = sorted({issue["swc-id"] for issue in issues})
    return rc, swcs, wall, (made[0] if made else None)


def device_arm(name: str, code: str) -> dict:
    """Warm the kernels the current policy selects, then analyze."""
    import jax

    from mythril_tpu.laser.tpu import backend
    from mythril_tpu.robustness import retry

    cfg = backend.DEFAULT_BATCH_CFG
    t0 = time.monotonic()
    warm_error = None
    try:
        backend.warmup_device(cfg)
    except Exception as e:  # reported as a failed check below
        warm_error = repr(e)
    record = {
        "phase": name + ":warmup",
        "compile_s": time.monotonic() - t0,
        "device_ready": backend.device_ready(cfg),
        "error": warm_error,
        "lanes": cfg.lanes,
    }
    emit(record)
    rc, swcs, wall, sym = analyze(code, "tpu-batch")
    strategy = backend.find_tpu_strategy(sym.laser.strategy) if sym else None
    stats = jax.devices()[0].memory_stats() or {}
    result = {
        "phase": name + ":analysis",
        "exit_code": rc,
        "swcs": swcs,
        "analysis_wall_s": wall,
        "breaker": retry.BREAKER.state(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "device_ready": record["device_ready"],
    }
    for field in (
        "device_rounds", "fused_rounds", "fused_syncs", "device_steps_retired",
        "degraded_rounds", "device_retries", "mesh_steal_events",
        "mesh_steal_lanes", "mesh_occupancy",
    ):
        result[field] = getattr(strategy, field, None)
    emit(result)
    return result


def device_checks(arm: dict) -> dict:
    return {
        "warmup": bool(arm["device_ready"]),
        "exit_code": arm["exit_code"] == 0,
        "device_rounds": (arm["device_rounds"] or 0) > 0,
        "fused_rounds": (arm["fused_rounds"] or 0) > 0,
        "device_steps": (arm["device_steps_retired"] or 0) > 0,
        "no_degraded_rounds": arm["degraded_rounds"] == 0,
        "no_device_retries": arm["device_retries"] == 0,
        "breaker_closed": arm["breaker"] == "closed",
    }


def one_chip(code: str) -> dict:
    from mythril_tpu.support.accelerators import host_chip_count

    import jax

    arm = device_arm("tpu-batch", code)
    rc, host_swcs, wall, _ = analyze(code, "bfs")
    emit({"phase": "host:analysis", "exit_code": rc, "swcs": host_swcs,
          "analysis_wall_s": wall})
    chips = host_chip_count()
    emit({"phase": "host-chips", "device_nodes": chips,
          "jax_devices": len(jax.devices())})
    checks = device_checks(arm)
    checks["host_exit_code"] = rc == 0
    checks["swc_parity"] = arm["swcs"] == host_swcs
    checks["chip_count"] = chips == len(jax.devices())
    return checks


def four_chips(code: str) -> dict:
    from mythril_tpu.laser.tpu import mesh as mesh_lib

    import jax

    mesh_arm = device_arm("mesh", code)
    shard_devices = [
        {"shard": i, "id": d.id, "device": str(d)}
        for i, d in enumerate(mesh_lib.make_mesh(len(jax.devices())).devices.ravel())
    ]
    n_shards = len(mesh_arm["mesh_occupancy"] or [])
    emit({"phase": "mesh:shards", "n_shards": n_shards,
          "shard_devices": shard_devices,
          "steal_events": mesh_arm["mesh_steal_events"],
          "steal_lanes": mesh_arm["mesh_steal_lanes"],
          "occupancy": mesh_arm["mesh_occupancy"]})
    saved = os.environ.get("MYTHRIL_TPU_MESH")
    os.environ["MYTHRIL_TPU_MESH"] = "off"
    try:
        off_arm = device_arm("single-device", code)
    finally:
        if saved is None:
            os.environ.pop("MYTHRIL_TPU_MESH")
        else:
            os.environ["MYTHRIL_TPU_MESH"] = saved
    checks = {"mesh:" + k: v for k, v in device_checks(mesh_arm).items()}
    checks.update(
        {"single-device:" + k: v for k, v in device_checks(off_arm).items()}
    )
    checks["n_shards"] = n_shards == 4
    checks["distinct_devices"] = len({d["id"] for d in shard_devices}) == 4
    checks["swc_parity"] = mesh_arm["swcs"] == off_arm["swcs"]
    return checks


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()
    device = {"platform": None, "kind": None, "count": 0}
    try:
        from mythril_tpu.laser.tpu import ensure_compile_cache
    except ImportError as e:
        print("chip_smoke: the repository is not importable here: %s" % e,
              file=sys.stderr)
        emit({"ok": False, "device": device, "failed": ["import"]})
        return 2
    ensure_compile_cache()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit({"phase": "device", **device,
          "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    code = creation_hex(CONTRACT)
    checks = {"platform": device["platform"] == "tpu",
              "count": device["count"] == args.chips}
    checks.update(one_chip(code) if args.chips == 1 else four_chips(code))
    failed = sorted(name for name, ok in checks.items() if not ok)
    if failed:
        emit({"ok": False, "device": device, "failed": failed})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
