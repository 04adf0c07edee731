"""Driver kind ``audit``: ``myth analyze --strategy tpu-batch``.

Each analysis goes through the entry ``core/mythril_analyzer.py`` uses,
``SymExecWrapper`` and then ``fire_lasers``, with the configuration's
settings and the program's default batch, on a fresh seeded redeploy of
the mix's contract, created from its creation code as the CLI does.

Set-up compiles the device kernels first (``backend.warmup_device``,
blocking; the product path warms up in the background instead), then
runs the analysis up to the transaction the mix opens its window on.

The mix holds one budget-bound analysis: the window opens at the start
of message-call transaction ``window_opens_at_tx``; everything before it
is set-up. At that moment the analysis budget is re-armed to
``--seconds`` through the program's own budget state (its time handler,
and the LaserEVM's budget clock that the round loops read), so the
analysis stops ``--seconds`` later and then reports. The window closes
after ``fire_lasers``. ``states_per_s`` is protocol v1: states explored
(host ``total_states`` plus device-retired instructions) over the whole
window.
"""

import datetime
import random
import time

from benchmark.lib.check import Analysis
from benchmark.lib.meter import SteadyStateMeter
from benchmark.lib.redeploy import load_contract, redeploy
from benchmark.lib.result import Result, phase_delta, phase_seconds

# the target address `myth analyze -c <creation code>` analyses at
CLI_ADDRESS = "0x" + "0" * 38 + "06"


def _annotation(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Analyzer:
    def __init__(self, settings: dict):
        from mythril_tpu.analysis.analysis_args import analysis_args
        from mythril_tpu.laser.tpu import backend

        self.settings = settings
        self.backend = backend
        analysis_args.set_loop_bound(settings["loop_bound"])
        analysis_args.set_solver_timeout(settings["solver_timeout"])

    def analyze(self, deployment, tx_count: int, pre_exec_hook=None):
        """One analysis; returns (Analysis, the SymExecWrapper or None)."""
        from mythril_tpu.analysis.security import fire_lasers
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.ethereum.evmcontract import EVMContract
        from mythril_tpu.support.start_time import StartTime

        s = self.settings
        StartTime()
        contract = EVMContract(creation_code=deployment.creation, name="MAIN")
        sym = None
        try:
            with _annotation("bench.sym_exec"):
                sym = SymExecWrapper(
                    contract, CLI_ADDRESS, s["strategy"],
                    max_depth=s["max_depth"],
                    execution_timeout=s["execution_timeout"],
                    loop_bound=s["loop_bound"],
                    create_timeout=s["create_timeout"],
                    transaction_count=tx_count,
                    compulsory_statespace=False,
                    pre_exec_hook=pre_exec_hook,
                )
            with _annotation("bench.fire_lasers"):
                issues = [issue.as_dict for issue in fire_lasers(sym)]
        except Exception as e:  # counted as a failed analysis
            return Analysis(deployment, tx_count, None, error=repr(e)), sym
        strategy = self.backend.find_tpu_strategy(sym.laser.strategy)
        steps = strategy.device_steps_retired if strategy is not None else 0
        return Analysis(deployment, tx_count, issues, device_steps=steps), sym


def run(run) -> Result:
    from mythril_tpu.laser.tpu import backend

    traffic = run.traffic
    result = Result()
    with _annotation("bench.warmup"):
        backend.warmup_device(backend.DEFAULT_BATCH_CFG)
    analyzer = _Analyzer(run.config["settings"])
    result.notes["lanes"] = backend.DEFAULT_BATCH_CFG.lanes
    contract = load_contract(traffic["contract"])
    dep = redeploy(traffic["contract"], contract["runtime"], random.Random(run.seed), 0)
    _deep(run, analyzer, dep, result)
    return result


def _deep(run, analyzer, dep, result: Result) -> None:
    from mythril_tpu.laser.evm.time_handler import time_handler

    opened = {}

    def on_open(laser):
        # re-arm the analysis budget to the window: the time handler
        # (solver budgets) and the LaserEVM budget clock (round loops)
        time_handler.start_execution(run.seconds)
        laser.execution_timeout = run.seconds
        laser.time = datetime.datetime.now()
        opened["setup_s"] = time.monotonic() - run.t0
        opened["phases"] = phase_seconds()
        opened["compiles"] = run.compiles.count
        run.start_trace()

    meter = SteadyStateMeter(open_at_tx=run.traffic["window_opens_at_tx"], on_open=on_open)
    analysis, sym = analyzer.analyze(dep, run.traffic["tx_count"], meter.install)
    meter.close()
    result.attempted = 1
    result.failed = int(analysis.error is not None)
    result.analyses = [analysis]
    result.hold(sym)
    if meter.t_open is None:
        # the window never opened: no measurement, and the check fails
        analysis.device_steps = 0
        result.setup_s = time.monotonic() - run.t0
        result.notes.update(window_opened=False, error=analysis.error)
        return
    analysis.device_steps = meter.device_steps
    result.setup_s = opened["setup_s"]
    result.window_s = meter.wall
    result.phase_s = phase_delta(opened["phases"], phase_seconds())
    result.e2e["states_per_s"] = meter.states_per_s
    strategy = analyzer.backend.find_tpu_strategy(sym.laser.strategy) if sym else None
    result.notes.update(
        tx_starts_s=[t - run.t0 for t in meter.tx_starts],
        window_s=meter.wall,
        window_states=meter.states,
        window_device_steps=meter.device_steps,
        device_rounds=getattr(strategy, "device_rounds", None),
        fused_rounds=getattr(strategy, "fused_rounds", None),
        fused_syncs=getattr(strategy, "fused_syncs", None),
        compiles_in_window=run.compiles.names[opened["compiles"]:],
        issues=sorted("%s@%s" % (i["swc-id"], i["address"]) for i in analysis.issues),
        phase_s=result.phase_s,
        error=analysis.error,
    )

