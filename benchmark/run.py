"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the workload names a configuration (``benchmark/configs/<config>.json``)
and a traffic mix (``benchmark/traffic/<mix>.json``); the mix names the
driver kind that runs it (``benchmark/drivers/<kind>.py``); each
per-layer metric is read by ``benchmark/metrics/<metric>.py``. A new
cell, mix, driver kind or metric is a new file and a new entry, never an
edit of a file that is here.

Set-up (compile cache, blocking device warmup, the mix's own prefix) is
timed as ``setup_s``; then the driver measures for ``--seconds``. With
``--trace 1`` a profiler trace of a short part of the window feeds the
per-layer metrics. Every analysis the window drove is then judged
against the plain reference (benchmark/lib/check.py), each compared
number printed beside its limit as the last lines of standard error.
The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"], "checks"}``.

The run fails (exit code 2, no result) when JAX finds no accelerator
or fewer chips than the cell asks for; ``JAX_PLATFORMS=cpu`` runs a
rehearsal on the CPU instead, stamped as such.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class BenchError(Exception):
    """The cell cannot run here; no result is printed."""


def note(**fields) -> None:
    """An informational line of standard output (not the result)."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError("no file %s" % os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(spec: dict, workload: str):
    """(end-to-end entries, per-layer entries) this cell reports."""
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}

    def reported(m) -> bool:
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in names

    return e2e, [m for m in spec["per_layer"] if reported(m)]


def resolve(spec: dict, workload: str, root: str = ROOT):
    """(cell, configuration, traffic) of a workload, each found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError("no workload %r in BENCHMARK.json" % workload)
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def cpu_requested() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def use_compile_cache() -> str:
    """The persistent compile cache at its fixed place in the checkout."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from mythril_tpu.laser.tpu import compile_cache_dir, ensure_compile_cache

    ensure_compile_cache()
    path = compile_cache_dir()
    if os.path.commonpath([path, ROOT]) != ROOT:
        raise BenchError("the compile cache %s lies outside the checkout" % path)
    return path


def devices_for(chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not cpu_requested():
        raise BenchError("JAX found no accelerator (platform %r)" % platform)
    if platform == "tpu" and len(devices) < chips:
        raise BenchError("the cell needs %d chips, JAX sees %d" % (chips, len(devices)))
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


class Run:
    """What a driver gets: the cell, its settings, and the run's clock."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, compiles, root=ROOT):
        self.root = root
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.compiles = compiles
        self.t0 = T0
        self.capture = None
        if trace:
            from benchmark.lib.trace import Capture

            self.capture = Capture(float(traffic.get("trace_s", 10)))

    def start_trace(self) -> None:
        """Open the traced part of the window (a no-op without --trace 1).
        The program's own span tracer runs with it, so idle gaps can be
        named by the round-loop phase the host was in."""
        if self.capture is not None and not self.capture.started:
            from mythril_tpu import obs

            obs.TRACER.enable()
            self.capture.start()


def read_trace(run: Run):
    """Reduce the run's profiler trace (benchmark/lib/trace.py), its idle
    gaps named by the program's own phase spans; None without a trace."""
    from benchmark.lib import trace as trace_lib

    xspace = run.capture.xspace if run.capture is not None else None
    if not xspace:
        return None
    t = time.monotonic()
    events = trace_lib.load_events(serialized=xspace)
    t_load = time.monotonic() - t
    spans = []
    traced = [ev for ev in events if ev.name == trace_lib.TRACED]
    if traced and run.capture.t_traced is not None:
        from mythril_tpu import obs

        spans = trace_lib.program_spans(obs.TRACER.raw_events(), run.capture.t_traced,
                                        traced[0].start_ns)
    reduced = trace_lib.reduce(events, host_spans=spans)
    note(trace_bytes=len(xspace), stop_trace_s=run.capture.stop_s, load_s=t_load,
         reduce_s=time.monotonic() - t - t_load, kept_events=len(events),
         trace={k: v for k, v in reduced.items() if k not in ("device_ops", "idle_gaps")})
    return reduced


def execute(run: Run, spec: dict, device: dict, read_peak=lambda: 0, tamper=None) -> dict:
    """Drive the cell, read its metrics and judge it; returns the result
    line. ``tamper``, when given, alters what the window produced before
    it is judged: the controls and the fault tests use it."""
    from benchmark.lib.check import judge, passed

    bench = os.path.join(run.root, "benchmark")
    kind = run.traffic["driver"]
    driver = load_module(os.path.join(bench, "drivers", kind + ".py"), "benchmark_driver_" + kind)
    result = driver.run(run)
    if run.capture is not None:
        run.capture.stop()
    device["memory_peak_bytes"] = read_peak()
    note(**result.notes)
    result.release()
    gc.collect()
    if tamper is not None:
        tamper(result)
    checks = judge(result.analyses, bool(run.traffic.get("device_required")))
    e2e, layer = cell_metrics(spec, run.cell["name"])
    metrics, breakdown = {}, None
    if run.trace:
        result.trace = read_trace(run)
        if result.trace is not None:
            device["busy_s"] = result.trace["busy_s"]
            device["window_s"] = result.trace["window_s"]
            breakdown = {"device_ops": result.trace["device_ops"],
                         "idle_gaps": result.trace["idle_gaps"]}
        result.memory_peak_bytes = device["memory_peak_bytes"]
        for m in layer:
            reader = load_module(os.path.join(bench, "metrics", m["name"] + ".py"),
                                 "benchmark_metric_" + re.sub(r"\W", "_", m["name"]))
            value = reader.read(result)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            value = result.setup_s if m["name"] == "setup_s" else result.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": passed(checks),
        "attempted": result.attempted,
        "failed": result.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def parse_args(argv=None, extra=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    if extra is not None:
        extra(parser)
    return parser.parse_args(argv)


def main(argv=None, extra=None, prepare=None) -> int:
    """``extra(parser)`` adds arguments; ``prepare(run, args)`` may change
    the run's settings and returns a tamper for execute(), or None. The
    control runs (benchmark/control.py) use both."""
    args = parse_args(argv, extra)
    try:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell, config, traffic = resolve(spec, args.workload)
        if not os.path.isdir(os.path.join(ROOT, "mythril_tpu")):
            raise BenchError("the system under test (mythril_tpu/) is not in this checkout")
        cache = use_compile_cache()
        devices = devices_for(int(cell["chips"]))
    except (BenchError, ImportError, OSError, KeyError, ValueError) as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 2
    from benchmark.lib.compiles import CompileCounter

    compiles = CompileCounter()
    compiles.install()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    note(workload=cell["name"], seed=args.seed, seconds=args.seconds,
         trace=args.trace, compile_cache=cache, device=device)
    run = Run(cell, config, traffic, args.seed, args.seconds, bool(args.trace), compiles)
    tamper = prepare(run, args) if prepare is not None else None
    line = execute(run, spec, device, lambda: memory_peak(devices), tamper)
    for name, c in line["checks"].items():
        print("check %s = %s (limit %s)" % (name, c["value"], c["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's daemon threads (service workers, solver pool) may
    # still hold native state; leave without interpreter teardown
    os._exit(code)
