"""Analysis orchestration.

Parity surface: mythril/mythril/mythril_analyzer.py (MythrilAnalyzer) —
the per-contract loop above SymExecWrapper/fire_lasers: run each loaded
contract, salvage partial results when a contract crashes or the user
interrupts, attach source mappings, and assemble the Report. Also hosts
the statespace-dump (-j) and CFG-graph (-g) commands."""

import logging
import traceback
from typing import List, Optional

from mythril_tpu.analysis.analysis_args import analysis_args
from mythril_tpu.analysis.report import Issue, Report
from mythril_tpu.analysis.security import fire_lasers, retrieve_callback_issues
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.exceptions import DeviceWarmupError
from mythril_tpu.laser.evm.iprof import InstructionProfiler
from mythril_tpu.support.source_support import Source
from mythril_tpu.support.start_time import StartTime

log = logging.getLogger(__name__)


class MythrilAnalyzer:
    def __init__(
        self,
        disassembler,
        requires_dynld: bool = False,
        use_onchain_data: bool = True,
        strategy: str = "bfs",
        address: Optional[str] = None,
        max_depth: Optional[int] = None,
        execution_timeout: Optional[int] = None,
        loop_bound: Optional[int] = None,
        create_timeout: Optional[int] = None,
        enable_iprof: bool = False,
        disable_dependency_pruning: bool = False,
        solver_timeout: Optional[int] = None,
        enable_coverage_strategy: bool = False,
        custom_modules_directory: str = "",
        checkpoint_dir: Optional[str] = None,
    ):
        self.eth = disassembler.eth
        self.contracts: List[EVMContract] = disassembler.contracts or []
        self.enable_online_lookup = disassembler.enable_online_lookup
        self.use_onchain_data = use_onchain_data
        self.strategy = strategy
        self.address = address
        self.max_depth = max_depth
        self.execution_timeout = execution_timeout
        self.loop_bound = loop_bound
        self.create_timeout = create_timeout
        self.iprof = InstructionProfiler() if enable_iprof else None
        self.disable_dependency_pruning = disable_dependency_pruning
        self.enable_coverage_strategy = enable_coverage_strategy
        self.custom_modules_directory = custom_modules_directory
        self.checkpoint_dir = checkpoint_dir
        analysis_args.set_loop_bound(loop_bound)
        analysis_args.set_solver_timeout(solver_timeout)

    # -- shared plumbing --------------------------------------------------------

    def _make_dynloader(self):
        from mythril_tpu.support.loader import DynLoader

        if not self.use_onchain_data or self.eth is None:
            return None
        return DynLoader(self.eth, active=self.use_onchain_data)

    def _wrapper_args(self, **overrides) -> dict:
        """The SymExecWrapper keyword set every command shares."""
        args = dict(
            checkpoint_dir=self.checkpoint_dir,
            dynloader=self._make_dynloader(),
            max_depth=self.max_depth,
            execution_timeout=self.execution_timeout,
            create_timeout=self.create_timeout,
            disable_dependency_pruning=self.disable_dependency_pruning,
            enable_coverage_strategy=self.enable_coverage_strategy,
            custom_modules_directory=self.custom_modules_directory,
        )
        args.update(overrides)
        return args

    # -- commands -----------------------------------------------------------------

    def dump_statespace(self, contract: Optional[EVMContract] = None) -> str:
        """Serialize the explored statespace as JSON (`-j`)."""
        import json

        from mythril_tpu.analysis.traceexplore import get_serializable_statespace

        sym = SymExecWrapper(
            contract or self.contracts[0],
            self.address,
            self.strategy,
            run_analysis_modules=False,
            **self._wrapper_args(),
        )
        return json.dumps(get_serializable_statespace(sym))

    def graph_html(
        self,
        contract: Optional[EVMContract] = None,
        enable_physics: bool = False,
        phrackify: bool = False,
        transaction_count: Optional[int] = None,
    ) -> str:
        """Interactive CFG html (`-g`)."""
        from mythril_tpu.analysis.callgraph import generate_graph

        sym = SymExecWrapper(
            contract or self.contracts[0],
            self.address,
            self.strategy,
            run_analysis_modules=False,
            **self._wrapper_args(transaction_count=transaction_count or 2),
        )
        return generate_graph(sym, physics=enable_physics, phrackify=phrackify)

    def fire_lasers(
        self,
        modules: Optional[List[str]] = None,
        transaction_count: Optional[int] = None,
    ) -> Report:
        """Analyze every loaded contract; salvage partial results on error."""
        all_issues: List[Issue] = []
        source_data = Source()
        source_data.get_source_from_contracts_list(self.contracts)
        exceptions = []

        for contract in self.contracts:
            StartTime()  # reset the execution clock per contract
            issues = self._analyze_one(contract, modules, transaction_count, exceptions)
            for issue in issues:
                issue.add_code_info(contract)
            all_issues += issues
            if self.iprof is not None:
                log.info("Instruction Statistics:\n%s", self.iprof)

        source_data.get_source_from_contracts_list(self.contracts)
        report = Report(contracts=self.contracts, exceptions=exceptions)
        for issue in all_issues:
            report.append_issue(issue)
        return report

    def _analyze_one(
        self, contract, modules, transaction_count, exceptions
    ) -> List[Issue]:
        """One contract through symexec + detectors, with salvage paths."""
        try:
            sym = SymExecWrapper(
                contract,
                self.address,
                self.strategy,
                loop_bound=self.loop_bound,
                transaction_count=transaction_count or 2,
                modules=modules,
                compulsory_statespace=False,
                iprof=self.iprof,
                **self._wrapper_args(),
            )
            return fire_lasers(sym, modules)
        except KeyboardInterrupt:
            log.critical("Keyboard Interrupt")
            return retrieve_callback_issues(modules)
        except DeviceWarmupError:
            # no salvage: a tpu-batch run without its device is an error,
            # and what the callback modules found so far must not leak
            # into the next analysis in this process
            retrieve_callback_issues(modules)
            raise
        except Exception:
            log.critical(
                "Exception occurred, aborting analysis. Please report this issue.\n"
                + traceback.format_exc()
            )
            exceptions.append(traceback.format_exc())
            return retrieve_callback_issues(modules)
