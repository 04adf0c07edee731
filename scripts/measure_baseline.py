#!/usr/bin/env python3
"""Measure the BASELINE.md driver-defined configs: host (bfs, the
reference's architecture) vs tpu-batch (the flagship mode), SWC parity
asserted per row.

Writes one JSON object per row to stdout and a summary table to stderr;
paste the table into BASELINE.md. It runs on the TPU and fails without
one, unless JAX_PLATFORMS=cpu asks for the CPU; the "platform" field
records which.

Usage: python scripts/measure_baseline.py [--budget SECONDS] [--rows a,b]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CORPUS = "/root/reference/tests/testdata/inputs"

# row name -> (sources, tx count, expected SWC ids that must appear)
ROWS = {
    "token_t2": ([("asm", "bench_contracts/token.asm")], 2, {"101"}),
    "suicide_origin_t3": (
        [("hex", CORPUS + "/suicide.sol.o"), ("hex", CORPUS + "/origin.sol.o")],
        3,
        {"106", "115"},
    ),
    "bectoken_t3": ([("asm", "bench_contracts/bectoken.asm")], 3, {"101"}),
    "multiowner_t4": ([("asm", "bench_contracts/multiowner.asm")], 4, {"106"}),
    "corpus_t2": (
        [
            ("hex", os.path.join(CORPUS, name))
            for name in (
                sorted(os.listdir(CORPUS)) if os.path.isdir(CORPUS) else []
            )
            if name.endswith(".sol.o")
        ],
        2,
        {"101", "104", "105", "106", "107", "110", "112", "115"},
    ),
}


def _git_rev() -> str:
    """Provenance stamp: merged rows from different code states must be
    tellable apart in BASELINE_MEASURED.json."""
    import subprocess

    try:
        return (
            subprocess.run(
                ["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
                capture_output=True,
                timeout=10,
            )
            .stdout.decode()
            .strip()
            or "unknown"
        )
    except Exception:
        return "unknown"


def _load(kind: str, path: str):
    from mythril_tpu.disassembler.asm import assemble
    from mythril_tpu.ethereum.evmcontract import EVMContract

    path = os.path.join(REPO, path) if not os.path.isabs(path) else path
    name = os.path.basename(path)
    if kind == "asm":
        runtime = assemble(open(path).read()).hex()
        n = len(runtime) // 2
        creation = (
            assemble(
                f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\nPUSH2 {n}\n"
                "PUSH1 0x00\nRETURN\ncode:"
            ).hex()
            + runtime
        )
        return EVMContract(code=runtime, creation_code=creation, name=name)
    return EVMContract(code=open(path).read().strip(), name=name)


def _run(contracts, tx: int, strategy: str, budget: int):
    """Benchmark protocol v1 (see support/benchmeter.py): per contract,
    the measured window runs from the first message-call round to the
    end of detection/witness solving; creation is excluded. Windows
    aggregate across a row's contracts."""
    from mythril_tpu.analysis.security import fire_lasers
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.support.benchmeter import SteadyStateMeter

    swcs = set()
    meter = SteadyStateMeter()
    for contract in contracts:
        sym = SymExecWrapper(
            contract,
            address=0x1234,
            strategy=strategy,
            execution_timeout=budget,
            transaction_count=tx,
            max_depth=128,
            pre_exec_hook=meter.install,
        )
        for issue in fire_lasers(sym):
            swcs.update(issue.swc_id.split())
        meter.close()
    return {
        "wall_s": round(meter.wall, 1),
        "states": meter.states,
        "states_per_s": round(meter.states_per_s, 1),
        "swcs": sorted(swcs),
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--budget", type=int, default=120)
    parser.add_argument("--rows", type=str, default=",".join(ROWS))
    args = parser.parse_args()

    sys.path.insert(0, REPO)
    # persistent compile cache BEFORE backend init: repeat invocations
    # must not pay the kernel compiles inside measured windows
    from mythril_tpu.laser.tpu import ensure_compile_cache

    ensure_compile_cache()
    import bench
    import mythril_tpu.laser.tpu.backend as backend

    platform = bench._device_info()["platform"]
    # measure throughput, not XLA compile latency
    backend.warmup_device(backend.DEFAULT_BATCH_CFG)

    results = {}
    for row in args.rows.split(","):
        sources, tx, expected = ROWS[row]
        contracts = [_load(kind, path) for kind, path in sources]
        if not contracts:
            print(f"{row}: no inputs found, skipped", file=sys.stderr)
            continue
        host = _run(contracts, tx, "bfs", args.budget)
        dev = _run(contracts, tx, "tpu-batch", args.budget)
        # sub-second windows are scheduler-noise-dominated (identical
        # code measured 1.26x and 0.81x on the same row); repeat tiny
        # rows and keep the MEDIAN rate per engine
        if host["wall_s"] + dev["wall_s"] < 10:
            hosts = [host] + [_run(contracts, tx, "bfs", args.budget) for _ in range(2)]
            devs = [dev] + [
                _run(contracts, tx, "tpu-batch", args.budget) for _ in range(2)
            ]
            host = sorted(hosts, key=lambda r: r["states_per_s"])[1]
            dev = sorted(devs, key=lambda r: r["states_per_s"])[1]
            # rate is the MEDIAN run's; detection is judged on the UNION
            # so parity never hinges on which rerun happened to be median
            host["swcs"] = sorted(set().union(*(r["swcs"] for r in hosts)))
            dev["swcs"] = sorted(set().union(*(r["swcs"] for r in devs)))
            host["runs"] = len(hosts)
            dev["runs"] = len(devs)
        parity = set(host["swcs"]) == set(dev["swcs"])
        found = expected <= set(dev["swcs"])
        results[row] = {
            "platform": platform,
            "protocol": "steady-state-v1",
            "rev": _git_rev(),
            "tx": tx,
            "host": host,
            "tpu_batch": dev,
            # null, not a sentinel-denominator absurdity, when the host
            # run starved inside creation (steady window empty)
            "integrated_vs_host": (
                round(dev["states_per_s"] / host["states_per_s"], 2)
                if host["states_per_s"] > 0
                else None
            ),
            "swc_parity": parity,
            "expected_found": found,
        }
        print(json.dumps({row: results[row]}), flush=True)
        status = "OK" if parity and found else "MISMATCH"
        print(
            f"{row:>20}  host {host['states_per_s']:>8}/s  "
            f"tpu-batch {dev['states_per_s']:>8}/s  "
            f"x{str(results[row]['integrated_vs_host']):<6} {status}",
            file=sys.stderr,
        )
    out = os.path.join(REPO, "BASELINE_MEASURED.json")
    # merge: a --rows subset run must not clobber the other rows'
    # baselines (downstream docs cite the whole table)
    merged = {}
    try:
        with open(out) as fh:
            merged = json.load(fh)
    except (OSError, ValueError):
        pass
    merged.update(results)
    with open(out, "w") as fh:
        json.dump(merged, fh, indent=1)
    print(f"wrote {out} ({len(results)} row(s) updated)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
