"""`myth` command line interface.

Parity surface: mythril/interfaces/cli.py (the reference's 827-line argparse
tree). This is a re-design, not a port: a declarative command registry maps
subcommand names to (argument-builder, runner) pairs, shared flag groups are
composed per command, and all output formatting funnels through one
``emit_report`` sink so text/markdown/json/jsonv2 stay consistent.

Subcommands:
  analyze (a)         symbolic-execution security analysis
  disassemble (d)     bytecode -> assembly listing
  serve               multi-tenant analysis service (stdin-JSON / socket)
  submit              submit bytecode to a running `myth serve` socket
  pro                 remote analysis through the MythX API
  list-detectors      registered detection modules
  version             package version
  function-to-hash    4-byte selector for a signature
  hash-to-address     last 20 bytes of a 32-byte hash as an address
  read-storage        read storage slots over RPC
"""

import argparse
import json
import logging
import os
import sys
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from mythril_tpu import __version__
from mythril_tpu.exceptions import CriticalError

log = logging.getLogger(__name__)

JSON_ERROR_OUTFORMS = ("json", "jsonv2")


def exit_with_error(outform: str, message: str) -> None:
    """Print an error in the requested format and exit(1)."""
    if outform == "json":
        print(json.dumps({"success": False, "error": message, "issues": []}))
    elif outform == "jsonv2":
        print(json.dumps([{"issues": [], "meta": {"logs": [{"level": "error", "hidden": True, "msg": message}]}}]))
    else:
        print(message, file=sys.stderr)
    sys.exit(1)


# --------------------------------------------------------------- flag groups


def add_input_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("input")
    group.add_argument("solidity_files", nargs="*", help=".sol files (suffix :ContractName to select one contract)")
    group.add_argument("-f", "--codefile", type=argparse.FileType("r"), help="file containing hex-encoded bytecode")
    group.add_argument("-c", "--code", help="hex-encoded creation bytecode string")
    group.add_argument("--bin-runtime", action="store_true", help="treat -c/-f input as runtime bytecode")
    group.add_argument("-a", "--address", help="on-chain contract address to load over RPC")
    group.add_argument("--solc-json", help="solc standard-json settings file")
    group.add_argument("--solv", help="solc version to use (requires matching binary on PATH)")


def add_rpc_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("networking")
    group.add_argument("--rpc", metavar="HOST:PORT / ganache / infura-<net>", help="custom RPC settings")
    group.add_argument("--rpctls", type=bool, default=False, help="RPC connection over TLS")
    group.add_argument("--infura-id", help="infura project id")


def add_output_flag(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "-o", "--outform", choices=("text", "markdown", "json", "jsonv2"), default="text",
        help="report output format",
    )


def add_analysis_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("analysis")
    group.add_argument(
        "--strategy",
        choices=(
            "dfs",
            "bfs",
            "naive-random",
            "weighted-random",
            "static-weighted",
            "tpu-batch",
        ),
        default="bfs",
        help="search strategy (tpu-batch = batched device backend; "
        "static-weighted = biased toward statically-interesting blocks)",
    )
    group.add_argument("-t", "--transaction-count", type=int, default=2, help="transaction depth")
    group.add_argument("-b", "--loop-bound", type=int, default=3, metavar="N", help="bound loops to N iterations")
    group.add_argument("--max-depth", type=int, default=128, help="maximum instruction depth per path")
    group.add_argument("--execution-timeout", type=int, default=86400, metavar="SEC", help="total symbolic execution budget")
    group.add_argument("--create-timeout", type=int, default=10, metavar="SEC", help="creation-transaction budget")
    group.add_argument("--solver-timeout", type=int, default=10000, metavar="MS", help="per-query solver budget")
    group.add_argument("-m", "--modules", metavar="MODULES", help="comma-separated detection module whitelist")
    group.add_argument("--no-onchain-data", action="store_true", help="never load code/storage over RPC")
    group.add_argument("-g", "--graph", metavar="HTML_FILE", help="write an interactive CFG graph")
    group.add_argument("--phrack", action="store_true", help="Phrack-style call graph")
    group.add_argument("--enable-physics", action="store_true", help="enable graph physics simulation")
    group.add_argument("-j", "--statespace-json", metavar="JSON_FILE", help="dump the explored statespace")
    group.add_argument("--enable-iprof", action="store_true", help="per-opcode instruction profiler")
    group.add_argument("--disable-dependency-pruning", action="store_true")
    group.add_argument("--enable-coverage-strategy", action="store_true")
    group.add_argument("--custom-modules-directory", default="", help="extra detection modules directory")
    group.add_argument("-q", "--query-signature", action="store_true", help="look up selectors on 4byte.directory")
    group.add_argument("--lanes", type=int, default=None, help="tpu-batch: device lanes per round")
    group.add_argument(
        "--checkpoint-dir",
        metavar="DIR",
        help="write an open-state checkpoint after every transaction round",
    )
    group.add_argument(
        "--trace",
        metavar="JSON_FILE",
        help="record round-loop spans and write a Chrome trace-event "
        "file (load in chrome://tracing or Perfetto)",
    )


# ------------------------------------------------------------------ plumbing


def _make_config(args):
    from mythril_tpu.core.mythril_config import MythrilConfig

    config = MythrilConfig()
    if getattr(args, "infura_id", None):
        config.set_api_infura_id(args.infura_id)
    if getattr(args, "address", None) or getattr(args, "command", "") == "read-storage":
        rpc = getattr(args, "rpc", None)
        if rpc:
            config.set_api_rpc(rpc, getattr(args, "rpctls", False))
        else:
            config.set_api_rpc_infura()
    return config


def _make_disassembler(args, config):
    from mythril_tpu.core.mythril_disassembler import MythrilDisassembler

    return MythrilDisassembler(
        eth=config.eth,
        solc_version=getattr(args, "solv", None),
        solc_settings_json=getattr(args, "solc_json", None),
        enable_online_lookup=getattr(args, "query_signature", False),
    )


def _load_code(args, disassembler) -> str:
    """Load the analysis target; returns the target address."""
    if args.code:
        address, _ = disassembler.load_from_bytecode(args.code, args.bin_runtime)
    elif args.codefile:
        bytecode = "".join([l.strip() for l in args.codefile if len(l.strip()) > 0])
        address, _ = disassembler.load_from_bytecode(bytecode, args.bin_runtime)
    elif args.address:
        address, _ = disassembler.load_from_address(args.address)
    elif args.solidity_files:
        address, _ = disassembler.load_from_solidity(args.solidity_files)
    else:
        raise CriticalError(
            "No input bytecode. Please provide EVM code via -c BYTECODE, -a ADDRESS, -f BYTECODE_FILE or a Solidity file"
        )
    return address


# ------------------------------------------------------------------ commands


def _make_analyzer(source, args, address=None, use_onchain_data=False):
    """Shared analyze/truffle plumbing: the --lanes batch override plus
    MythrilAnalyzer construction from the analysis flag group."""
    from mythril_tpu.core.mythril_analyzer import MythrilAnalyzer

    if args.lanes:
        import mythril_tpu.laser.tpu.backend as backend

        backend.DEFAULT_BATCH_CFG = backend.DEFAULT_BATCH_CFG._replace(
            lanes=args.lanes
        )
    return MythrilAnalyzer(
        source,
        strategy=args.strategy,
        address=address,
        max_depth=args.max_depth,
        execution_timeout=args.execution_timeout,
        loop_bound=args.loop_bound,
        create_timeout=args.create_timeout,
        enable_iprof=args.enable_iprof,
        disable_dependency_pruning=args.disable_dependency_pruning,
        solver_timeout=args.solver_timeout,
        enable_coverage_strategy=args.enable_coverage_strategy,
        custom_modules_directory=args.custom_modules_directory,
        use_onchain_data=use_onchain_data,
        checkpoint_dir=args.checkpoint_dir,
    )


def _run_analysis(analyzer, args) -> None:
    """Shared analysis tail: -g/-j exports or the full detection run."""
    trace_path = getattr(args, "trace", None)
    if trace_path:
        from mythril_tpu import obs

        obs.TRACER.enable()
    try:
        _run_analysis_inner(analyzer, args)
    finally:
        if trace_path:
            n = obs.TRACER.export(trace_path)
            print(
                "wrote %d trace events to %s" % (n, trace_path),
                file=sys.stderr,
            )


def _run_analysis_inner(analyzer, args) -> None:
    if args.graph:
        html = analyzer.graph_html(
            transaction_count=args.transaction_count,
            enable_physics=args.enable_physics,
            phrackify=args.phrack,
        )
        with open(args.graph, "w") as f:
            f.write(html)
        return
    if args.statespace_json:
        dump = analyzer.dump_statespace()
        with open(args.statespace_json, "w") as f:
            f.write(dump)
        return
    modules = args.modules.split(",") if args.modules else None
    report = analyzer.fire_lasers(
        modules=modules, transaction_count=args.transaction_count
    )
    emit_report(report, args.outform)


def run_analyze(args) -> None:
    config = _make_config(args)
    disassembler = _make_disassembler(args, config)
    address = _load_code(args, disassembler)
    analyzer = _make_analyzer(
        disassembler,
        args,
        address=address,
        use_onchain_data=not args.no_onchain_data,
    )
    _run_analysis(analyzer, args)


def emit_report(report, outform: str) -> None:
    renderers: Dict[str, Callable[[], str]] = {
        "text": report.as_text,
        "markdown": report.as_markdown,
        "json": report.as_json,
        "jsonv2": report.as_swc_standard_format,
    }
    print(renderers[outform]())


def run_pro(args) -> None:
    """Remote analysis through the MythX API (reference cli.py:229)."""
    from mythril_tpu import mythx
    from mythril_tpu.analysis.report import Report

    config = _make_config(args)
    disassembler = _make_disassembler(args, config)
    _load_code(args, disassembler)
    issues = mythx.analyze(disassembler.contracts, args.mode)
    report = Report(contracts=disassembler.contracts)
    for issue in issues:
        report.append_issue(issue)
    emit_report(report, args.outform)


def run_disassemble(args) -> None:
    config = _make_config(args)
    disassembler = _make_disassembler(args, config)
    _load_code(args, disassembler)
    contract = disassembler.contracts[0]
    listing = contract.get_easm()
    if listing:
        print("Runtime Disassembly:\n" + listing)
    creation = getattr(contract, "creation_disassembly", None)
    if creation is not None and getattr(creation, "instruction_list", None):
        from mythril_tpu.disassembler.asm import instruction_list_to_easm

        print("Creation Disassembly:\n" + instruction_list_to_easm(creation.instruction_list))
    elif not listing:
        raise CriticalError("No code to disassemble")


def run_list_detectors(args) -> None:
    from mythril_tpu.analysis.module.loader import ModuleLoader

    modules = []
    for module in ModuleLoader().get_detection_modules():
        modules.append({"classname": type(module).__name__, "title": module.name})
    if args.outform in ("json", "jsonv2"):
        print(json.dumps(modules))
    else:
        for module_data in modules:
            print("{}: {}".format(module_data["classname"], module_data["title"]))


def run_version(args) -> None:
    if args.outform in ("json", "jsonv2"):
        print(json.dumps({"version_str": "v" + __version__}))
    else:
        print("Mythril-TPU version v{}".format(__version__))


def run_function_to_hash(args) -> None:
    from mythril_tpu.core.mythril_disassembler import MythrilDisassembler

    print(MythrilDisassembler.hash_for_function_signature(args.func))


def run_hash_to_address(args) -> None:
    value = args.hash
    if value.startswith("0x"):
        value = value[2:]
    if len(value) != 64:
        raise CriticalError("Invalid hash. Expected a 32-byte hex string")
    print("0x" + value[-40:])


def run_read_storage(args) -> None:
    config = _make_config(args)
    disassembler = _make_disassembler(args, config)
    outtxt = disassembler.get_state_variable_from_storage(
        address=args.address, params=args.storage_slots.split(",")
    )
    print(outtxt)


def run_leveldb_search(args) -> None:
    """Regex-search stored contract code in a local geth LevelDB
    (reference cli.py:247 dispatch + :559 leveldb_search)."""
    from mythril_tpu.core.mythril_leveldb import MythrilLevelDB

    config = _make_config(args)
    leveldb_dir = args.leveldb_dir or config.leveldb_dir
    try:
        searcher = MythrilLevelDB(config.set_api_leveldb(leveldb_dir))
    except (OSError, ValueError, NotImplementedError, ImportError) as e:
        raise CriticalError(f"Could not open LevelDB at {leveldb_dir!r}: {e}")
    searcher.search_db(args.search)


def run_truffle(args) -> None:
    """Analyze a truffle project from its build artifacts (reference
    cli.py:264 subcommand / :386 --truffle flag): reads
    build/contracts/*.json in the project dir and runs the same
    analysis pipeline over each deployed contract."""
    import glob

    from mythril_tpu.ethereum.evmcontract import EVMContract

    project_dir = args.project_dir or os.getcwd()
    artifacts = sorted(
        glob.glob(os.path.join(project_dir, "build", "contracts", "*.json"))
    )
    if not artifacts:
        raise CriticalError(
            "No truffle build artifacts found (expected "
            "build/contracts/*.json under %r). Run `truffle compile` "
            "first, or pass --project-dir." % project_dir
        )
    contracts = []
    for path in artifacts:
        try:
            with open(path) as fh:
                artifact = json.load(fh)
        except (OSError, ValueError) as e:
            log_msg = "Skipping unreadable artifact %s: %s" % (path, e)
            logging.getLogger(__name__).warning(log_msg)
            continue
        def strip0x(value):
            value = (value or "").strip()
            return value[2:] if value.startswith("0x") else value

        deployed = strip0x(artifact.get("deployedBytecode"))
        creation = strip0x(artifact.get("bytecode"))
        if not deployed:
            continue  # interfaces/abstract contracts have no runtime code
        contracts.append(
            EVMContract(
                code=deployed,
                creation_code=creation,
                name=artifact.get("contractName") or os.path.basename(path),
            )
        )
    if not contracts:
        raise CriticalError("No deployable contracts in the truffle artifacts")

    class _TruffleSource:
        """Duck-typed disassembler facade over the loaded artifacts."""

        eth = None
        enable_online_lookup = False

        def __init__(self, loaded):
            self.contracts = loaded

    # same placeholder target address load_from_bytecode uses: artifacts
    # with runtime code but no creation code take the message-call path,
    # which needs a concrete callee
    analyzer = _make_analyzer(
        _TruffleSource(contracts), args, address="0x" + "0" * 38 + "06"
    )
    _run_analysis(analyzer, args)


def add_serve_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("service")
    group.add_argument("--socket", metavar="PATH", help="serve over a Unix domain socket instead of stdin-JSON")
    group.add_argument("--workers", type=int, default=2, help="concurrent analysis jobs (each may share device rounds)")
    group.add_argument("--queue-size", type=int, default=16, help="bounded job queue; submissions beyond this are rejected")
    group.add_argument("--gather-window", type=float, default=0.25, metavar="SEC", help="how long a device round waits to co-schedule other jobs' frontiers")
    group.add_argument("--cache-entries", type=int, default=256, help="result-cache capacity (contracts)")
    group.add_argument("--no-warm", action="store_true", help="skip the blocking device-kernel warmup at startup")
    group.add_argument("--lanes", type=int, default=None, help="device lanes per shared round")
    group.add_argument("--store", metavar="DIR", help="durable warm-store directory (docs/FLEET.md); results, solver memos and quarantine strikes persist there and are shared with other workers on the same directory")


def run_serve(args) -> None:
    """The multi-tenant analysis service (docs/SERVICE.md): one process,
    many submitted contracts, shared device rounds, cached results.
    With --store, the warm tier is durable and fleet-shared
    (docs/FLEET.md)."""
    import mythril_tpu.laser.tpu.backend as backend
    from mythril_tpu.service import AnalysisService
    from mythril_tpu.service.api import SocketServer, serve_stdio

    if args.lanes:
        backend.DEFAULT_BATCH_CFG = backend.DEFAULT_BATCH_CFG._replace(
            lanes=args.lanes
        )
    cache = None
    if getattr(args, "store", None):
        from mythril_tpu.fleet.store import DurableResultCache
        from mythril_tpu.obs import catalog as _catalog

        cache = DurableResultCache(
            args.store, max_entries=args.cache_entries
        )
        _catalog.register_store(cache)
        print("durable store at %s" % args.store, file=sys.stderr)
    service = AnalysisService(
        workers=args.workers,
        queue_size=args.queue_size,
        gather_window_s=args.gather_window,
        cache_entries=args.cache_entries,
        warm=not args.no_warm,
        cache=cache,
    )
    try:
        if args.socket:
            server = SocketServer(service, args.socket)
            print("serving on %s" % args.socket, file=sys.stderr)
            server.serve_forever()
        else:
            serve_stdio(service, sys.stdin, sys.stdout)
    except KeyboardInterrupt:
        pass
    finally:
        service.shutdown(wait=False)
        if cache is not None:
            cache.close()


def add_submit_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("submission")
    group.add_argument("--socket", metavar="PATH", required=True, help="socket of a running `myth serve --socket`")
    group.add_argument("-c", "--code", help="hex-encoded creation bytecode string")
    group.add_argument("-f", "--codefile", type=argparse.FileType("r"), help="file containing hex-encoded bytecode")
    group.add_argument("--bin-runtime", action="store_true", help="treat the input as runtime bytecode")
    group.add_argument("--name", default="contract", help="contract name used in the report")
    group.add_argument("-t", "--transaction-count", type=int, default=2, help="transaction depth")
    group.add_argument("--execution-timeout", type=int, default=60, metavar="SEC", help="per-job symbolic execution budget")
    group.add_argument("-m", "--modules", metavar="MODULES", help="comma-separated detection module whitelist")
    group.add_argument("--no-wait", action="store_true", help="print the job id and return without waiting for the result")
    group.add_argument("--trace", metavar="JSON_FILE", help="ask the service for this job's span timeline and write it as a Chrome trace-event file")


def run_submit(args) -> None:
    """Client for a running service: submit bytecode, print the result."""
    from mythril_tpu.service.api import request_over_socket

    code = args.code or ""
    if args.codefile:
        code = "".join(line.strip() for line in args.codefile if line.strip())
    if not code:
        raise CriticalError(
            "No input bytecode. Provide EVM code via -c BYTECODE or -f BYTECODE_FILE"
        )
    request = {
        "op": "submit",
        "name": args.name,
        "tx_count": args.transaction_count,
        "timeout": args.execution_timeout,
    }
    if args.bin_runtime:
        request["code"] = code
    else:
        request["creation_code"] = code
    if args.modules:
        request["modules"] = args.modules.split(",")
    if args.trace:
        request["trace"] = True
    response = request_over_socket(args.socket, request, timeout=30)
    if not response.get("ok"):
        raise CriticalError("submission rejected: %s" % response.get("error"))
    if args.no_wait:
        print(json.dumps(response))
        return
    result = request_over_socket(
        args.socket, {"op": "result", "job_id": response["job_id"]}
    )
    if args.trace:
        events = (result.get("result") or {}).pop("trace_events", [])
        with open(args.trace, "w") as f:
            json.dump(
                {"traceEvents": events, "displayTimeUnit": "ms"}, f
            )
        print(
            "wrote %d trace events to %s" % (len(events), args.trace),
            file=sys.stderr,
        )
    print(json.dumps(result, indent=2))


def add_top_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("monitoring")
    target = group.add_mutually_exclusive_group(required=True)
    target.add_argument("--socket", metavar="PATH", help="socket of a running `myth serve --socket`")
    target.add_argument("--gateway", metavar="HOST:PORT", help="address of a running `myth gateway` (fleet-wide view)")
    group.add_argument("--interval", type=float, default=0.0, metavar="SEC", help="refresh every SEC seconds (default: print once and exit)")
    group.add_argument("--count", type=int, default=0, metavar="N", help="with --interval: stop after N refreshes (default: until interrupted)")


def _parse_prometheus(text: str) -> Dict[str, float]:
    """Flatten exposition text to {name{labels}: value} (`myth top`
    only needs point lookups, not a real scrape parser)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            continue
    return out


def _render_top(stats: Dict, prom: Dict[str, float]) -> str:
    """One console frame: the operator's five questions (queue depth,
    lanes resident, warm-cache rate, solver hit rate, degraded rounds)
    answered on five lines."""

    def rate(hits: float, total: float) -> str:
        return "%.0f%%" % (100.0 * hits / total) if total else "-"

    solver_q = prom.get("myth_solver_queries_total", 0.0)
    solver_hits = sum(
        v for k, v in prom.items()
        if k.startswith("myth_solver_hits_total")
    )
    cache = stats.get("cache", {})
    lines = [
        "jobs      submitted %d   done %d   failed %d   cancelled %d   retried %d"
        % (
            stats.get("jobs_submitted", 0), stats.get("jobs_done", 0),
            stats.get("jobs_failed", 0), stats.get("jobs_cancelled", 0),
            stats.get("jobs_retried", 0),
        ),
        "queue     depth %d   resident peak %d   shared rounds %d/%d"
        % (
            stats.get("queued", 0), stats.get("max_resident_jobs", 0),
            stats.get("shared_rounds", 0), stats.get("rounds", 0),
        ),
        "device    degraded rounds %d   retries %d   breaker %s (trips %d)"
        % (
            stats.get("degraded_rounds", 0), stats.get("device_retries", 0),
            stats.get("breaker_state", "?"), stats.get("breaker_trips", 0),
        ),
        "caches    warm results %s (%d/%d, %d entries)   solver hits %s (%d/%d)"
        % (
            rate(cache.get("hits", 0), cache.get("hits", 0) + cache.get("misses", 0)),
            cache.get("hits", 0),
            cache.get("hits", 0) + cache.get("misses", 0),
            cache.get("entries", 0),
            rate(solver_hits, solver_q), solver_hits, solver_q,
        ),
        "safety    quarantined %d   checkpoints %d (%.2fs overhead)"
        % (
            stats.get("quarantined_jobs", 0), stats.get("checkpoints", 0),
            stats.get("checkpoint_overhead_s", 0.0),
        ),
    ]
    return "\n".join(lines)


def _render_fleet_top(fleet: Dict) -> str:
    """One console frame for a whole fleet: gateway posture, admission
    level, then one line per worker."""
    gw = fleet.get("gateway", {})
    adm = fleet.get("admission", {})
    lines = [
        "gateway   workers %d/%d alive   deaths %d   reroutes %d   jobs placed %d   up %.0fs"
        % (
            gw.get("workers_alive", 0), gw.get("workers", 0),
            gw.get("worker_deaths", 0), gw.get("reroutes", 0),
            gw.get("placements", 0), gw.get("uptime_s", 0.0),
        ),
        "admission level %.2f   queue pressure %.0f%%   warm rate %.0f%%   breaker %s   admitted %d   shed %d"
        % (
            adm.get("level", 0.0),
            100.0 * adm.get("queue_pressure", 0.0),
            100.0 * adm.get("warm_rate", 0.0),
            "OPEN" if adm.get("breaker_open") else "closed",
            adm.get("admitted", 0), adm.get("shed", 0),
        ),
    ]
    for name in sorted(fleet.get("workers") or {}):
        stats = (fleet["workers"] or {}).get(name)
        if not stats:
            lines.append("  %-10s DEAD" % name)
            continue
        cache = stats.get("cache", {})
        total = cache.get("hits", 0) + cache.get("misses", 0)
        lines.append(
            "  %-10s queued %d   done %d   failed %d   warm %s   breaker %s"
            % (
                name, stats.get("queued", 0), stats.get("jobs_done", 0),
                stats.get("jobs_failed", 0),
                "%.0f%%" % (100.0 * cache.get("hits", 0) / total)
                if total else "-",
                stats.get("breaker_state", "?"),
            )
        )
    return "\n".join(lines)


def run_top(args) -> None:
    """Live metrics console: one service (--socket) or a whole fleet
    through its gateway (--gateway). One-shot by default, a refreshing
    view with --interval (docs/OBSERVABILITY.md, docs/FLEET.md)."""
    import time as _time

    shown = 0
    while True:
        if args.gateway:
            from mythril_tpu.fleet import transport

            fleet = transport.request(
                args.gateway, {"op": "fleet_stats"}, timeout=10
            )
            if not fleet.get("ok"):
                raise CriticalError(
                    "gateway query failed: %s" % fleet.get("error")
                )
            frame = _render_fleet_top(fleet)
        else:
            from mythril_tpu.service.api import request_over_socket

            stats = request_over_socket(args.socket, {"op": "stats"}, timeout=10)
            metrics = request_over_socket(args.socket, {"op": "metrics"}, timeout=10)
            if not stats.get("ok") or not metrics.get("ok"):
                raise CriticalError(
                    "service query failed: %s"
                    % (stats.get("error") or metrics.get("error"))
                )
            frame = _render_top(stats, _parse_prometheus(metrics["metrics"]))
        if args.interval and shown:
            print()
        print(frame)
        shown += 1
        if not args.interval or (args.count and shown >= args.count):
            return
        _time.sleep(args.interval)


def add_gateway_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("fleet gateway")
    group.add_argument("--listen", metavar="HOST:PORT", default="127.0.0.1:8551", help="TCP address to serve on (line-JSON protocol with HTTP sniffing)")
    group.add_argument("--worker", metavar="NAME=ADDR", action="append", default=[], help="attach an existing worker (repeatable); ADDR is a socket path or host:port")
    group.add_argument("--spawn", type=int, default=0, metavar="N", help="additionally spawn N local worker processes (`myth serve`)")
    group.add_argument("--store", metavar="DIR", help="shared durable store directory for spawned workers")
    group.add_argument("--spawn-queue-size", type=int, default=16, help="job queue size for spawned workers")
    group.add_argument("--warm", action="store_true", help="spawned workers run the device warmup at startup")
    group.add_argument("--rate", type=float, default=8.0, metavar="PER_SEC", help="base per-tenant admission rate")
    group.add_argument("--burst", type=float, default=16.0, help="per-tenant admission burst")


def run_gateway(args) -> None:
    """The fleet front gateway (docs/FLEET.md): routes submissions over
    a consistent-hash ring of workers, re-routes jobs off dead workers,
    streams watch events, and sheds load per tenant."""
    import atexit
    import tempfile

    from mythril_tpu.fleet.gateway import Gateway, GatewayServer
    from mythril_tpu.fleet.qos import AdmissionController
    from mythril_tpu.fleet.worker import (
        SocketWorker, device_slots, spawn_worker, wait_for_socket,
    )

    workers = []
    for spec in args.worker:
        name, sep, addr = spec.partition("=")
        if not sep:
            raise CriticalError(
                "--worker wants NAME=ADDR, got %r" % spec
            )
        workers.append(SocketWorker(name, addr))
    procs = []
    if args.spawn:
        try:
            chips = device_slots(args.spawn)
        except ValueError as e:
            raise CriticalError(str(e))
        run_dir = tempfile.mkdtemp(prefix="myth-fleet-")
        for i, chip in enumerate(chips):
            sock = os.path.join(run_dir, "worker%d.sock" % i)
            procs.append(spawn_worker(
                sock, store_dir=args.store,
                queue_size=args.spawn_queue_size, warm=args.warm, chip=chip,
            ))
            workers.append(SocketWorker("worker%d" % i, sock))
        for proc, worker in zip(procs, workers[-args.spawn:]):
            print("waiting for %s ..." % worker.address, file=sys.stderr)
            wait_for_socket(worker.address, process=proc)

        def _reap():
            for proc in procs:
                proc.terminate()
        atexit.register(_reap)
    if not workers:
        raise CriticalError(
            "no workers: pass --worker NAME=ADDR and/or --spawn N"
        )
    host, _, port = args.listen.rpartition(":")
    gateway = Gateway(
        workers,
        admission=AdmissionController(
            base_rate_per_s=args.rate, burst=args.burst
        ),
    )
    gateway.start()
    server = GatewayServer(gateway, host or "127.0.0.1", int(port))
    print(
        "gateway on %s (%d workers)" % (server.address, len(workers)),
        file=sys.stderr,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        gateway.stop()


def add_scan_flags(parser: argparse.ArgumentParser) -> None:
    group = parser.add_argument_group("chain scan")
    group.add_argument("--gateway", metavar="HOST:PORT", required=True, help="address of a running `myth gateway`")
    group.add_argument("-n", "--contracts", type=int, default=20, help="number of deployments to scan")
    group.add_argument("--seed", type=int, default=1337, help="RNG seed (corpus choice, dup choice, metadata bytes)")
    group.add_argument("--dup-rate", type=float, default=0.4, help="probability a deployment is an exact re-submission")
    group.add_argument("--rate", type=float, default=0.0, metavar="PER_SEC", help="client-side submission rate limit (0 = unthrottled)")
    group.add_argument("--watch-fraction", type=float, default=0.25, help="fraction of submissions that also open a watch stream")
    group.add_argument("--tenant", default="chain-scan", help="tenant name for QoS accounting")
    group.add_argument("-t", "--transaction-count", type=int, default=2, help="transaction depth per contract")
    group.add_argument("--execution-timeout", type=int, default=60, metavar="SEC", help="per-job symbolic execution budget")


def run_scan(args) -> None:
    """Chain-scan ingest (docs/FLEET.md): stream a synthetic block-
    explorer workload (near-duplicate deployments) at a fleet gateway
    and report throughput, latency, and warm-tier absorption."""
    from mythril_tpu.fleet.ingest import ChainScan
    from mythril_tpu.fleet.worker import SocketWorker

    scan = ChainScan(
        SocketWorker("gateway", args.gateway),
        seed=args.seed,
        dup_rate=args.dup_rate,
        rate_per_s=args.rate,
        watch_fraction=args.watch_fraction,
        tenant=args.tenant,
        tx_count=args.transaction_count,
        timeout=args.execution_timeout,
    )
    summary = scan.run(args.contracts)
    print(json.dumps(summary, indent=2))
    if summary["completed"] == 0:
        raise CriticalError("chain scan completed 0 contracts")


# ------------------------------------------------------------------ registry

COMMANDS: Dict[str, Tuple[str, List[Callable], Callable]] = {
    # name: (help, [flag group builders], runner)
    "analyze": (
        "Triggers the symbolic-execution security analysis",
        [add_input_flags, add_rpc_flags, add_output_flag, add_analysis_flags],
        run_analyze,
    ),
    "disassemble": (
        "Disassembles the input bytecode",
        [add_input_flags, add_rpc_flags, add_output_flag],
        run_disassemble,
    ),
    "serve": (
        "Runs the multi-tenant analysis service",
        [add_serve_flags],
        run_serve,
    ),
    "submit": (
        "Submits bytecode to a running analysis service",
        [add_submit_flags],
        run_submit,
    ),
    "top": (
        "Shows live metrics from a running analysis service",
        [add_top_flags],
        run_top,
    ),
    "gateway": (
        "Runs the fleet front gateway over analysis workers",
        [add_gateway_flags],
        run_gateway,
    ),
    "scan": (
        "Streams a chain-scan ingest workload at a fleet gateway",
        [add_scan_flags],
        run_scan,
    ),
    "pro": (
        "Analyzes input with the MythX API (https://mythx.io)",
        [
            add_input_flags,
            add_rpc_flags,
            add_output_flag,
            lambda p: p.add_argument(
                "--mode",
                choices=("quick", "standard", "deep"),
                default="quick",
                help="MythX analysis mode",
            ),
        ],
        run_pro,
    ),
    "list-detectors": (
        "Lists the available detection modules",
        [add_output_flag],
        run_list_detectors,
    ),
    "version": ("Prints the version", [add_output_flag], run_version),
    "function-to-hash": (
        "4-byte selector for a function signature",
        [lambda p: p.add_argument("func", help="signature, e.g. 'transfer(address,uint256)'")],
        run_function_to_hash,
    ),
    "hash-to-address": (
        "Address form of a 32-byte hash",
        [lambda p: p.add_argument("hash", help="32-byte hex hash")],
        run_hash_to_address,
    ),
    "read-storage": (
        "Read state variables from on-chain storage",
        [
            lambda p: p.add_argument("storage_slots", help="position[,length] or mapping math"),
            lambda p: p.add_argument("address", help="contract address"),
            add_rpc_flags,
            add_output_flag,
        ],
        run_read_storage,
    ),
    "leveldb-search": (
        "Searches the code fragment in local leveldb",
        [
            lambda p: p.add_argument("search", help="regex over contract code"),
            lambda p: p.add_argument(
                "--leveldb-dir",
                help="path to the geth chaindata LevelDB (default from config.ini)",
            ),
            add_output_flag,
        ],
        run_leveldb_search,
    ),
    "truffle": (
        "Analyze a truffle project from its build artifacts",
        [
            lambda p: p.add_argument(
                "--project-dir",
                help="truffle project root (default: current directory)",
            ),
            add_output_flag,
            add_analysis_flags,
        ],
        run_truffle,
    ),
}

ALIASES = {"a": "analyze", "d": "disassemble"}


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="myth",
        description="Mythril-TPU: security analysis of EVM bytecode on TPU",
    )
    parser.add_argument("--version", action="version", version="v" + __version__)
    parser.add_argument("-v", metavar="LOG_LEVEL", type=int, default=2, dest="verbosity",
                        help="log level 0 (silent) .. 5 (trace)")
    parser.add_argument("--epic", action="store_true",
                        help=argparse.SUPPRESS)  # rainbow output (easter egg)
    subparsers = parser.add_subparsers(dest="command")
    for name, (help_text, flag_builders, _runner) in COMMANDS.items():
        aliases = [a for a, target in ALIASES.items() if target == name]
        sub = subparsers.add_parser(name, help=help_text, aliases=aliases)
        for builder in flag_builders:
            builder(sub)
    return parser


def _set_verbosity(level: int) -> None:
    levels = {
        0: logging.CRITICAL, 1: logging.ERROR, 2: logging.WARNING,
        3: logging.INFO, 4: logging.DEBUG, 5: logging.DEBUG,
    }
    logging.basicConfig(level=levels.get(level, logging.WARNING))
    logging.getLogger("jax").setLevel(logging.ERROR)


def main(argv: Optional[List[str]] = None) -> None:
    # persistent XLA compile cache: the device kernels take tens of
    # seconds to compile; repeat CLI invocations should pay that once
    from mythril_tpu.laser.tpu import ensure_compile_cache

    ensure_compile_cache()
    parser = build_parser()
    args = parser.parse_args(argv)
    command = ALIASES.get(args.command, args.command)
    if command is None:
        parser.print_help()
        sys.exit(2)
    _set_verbosity(args.verbosity)
    if args.epic:
        from mythril_tpu.interfaces import epic

        # TTY-gated: piped/redirected output (-o json in CI) stays clean
        epic.engage()
    outform = getattr(args, "outform", "text")
    exit_code = 0
    try:
        COMMANDS[command][2](args)
    except (CriticalError, KeyboardInterrupt) as e:
        msg = str(e) if isinstance(e, CriticalError) else "Analysis was interrupted"
        try:
            exit_with_error(outform, msg)
        except SystemExit as se:
            exit_code = se.code if isinstance(se.code, int) else 1
    except SystemExit as e:
        exit_code = e.code if isinstance(e.code, int) else (1 if e.code else 0)
    except BaseException:
        # traceback must print BEFORE the hard-exit check below — a
        # finally: os._exit would swallow it
        traceback.print_exc()
        exit_code = 1
    _hard_exit_if_compiling(exit_code)
    if exit_code:
        sys.exit(exit_code)


def _hard_exit_if_compiling(code: int) -> None:
    """Skip interpreter finalization while a device-kernel compile is in
    flight on a background thread (tpu-batch warmup, laser/tpu/backend).

    The analysis deliberately does not wait for a slow XLA compile, so
    at exit time the warmup thread can still be tracing/compiling;
    CPython teardown while that native work runs intermittently corrupts
    the heap (observed: glibc "double free or corruption" after results
    were already printed). Results are out, so a hard exit loses
    nothing."""
    backend = sys.modules.get("mythril_tpu.laser.tpu.backend")
    if backend is not None and backend.warmup_pending():
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)


if __name__ == "__main__":
    main()
