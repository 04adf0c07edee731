"""The LASER symbolic EVM engine.

Parity surface: mythril/laser/ethereum/svm.py (LaserEVM). The engine owns
the work list and the hook surface; one `exec` iteration selects a state
through the strategy stack, evaluates a single instruction, filters
infeasible successors, maintains the CFG, and extends the work list.
Nested calls and transaction ends arrive as signal exceptions from the
instruction layer and are turned into frame pushes/pops here.

With `--strategy tpu-batch` selected, message-call rounds run through the
hybrid host/device loop instead (mythril_tpu/laser/tpu/backend.py) — same
hook surface, frontier-at-a-time scheduling."""

import logging
from collections import defaultdict
from copy import copy
from datetime import datetime, timedelta
from typing import Callable, DefaultDict, Dict, List, Optional, Tuple

from mythril_tpu import obs
from mythril_tpu.laser.evm.cfg import Edge, JumpType, Node, NodeFlags
from mythril_tpu.laser.evm.evm_exceptions import StackUnderflowException, VmException
from mythril_tpu.laser.evm.instructions import Instruction
from mythril_tpu.laser.evm.plugins.signals import PluginSkipState, PluginSkipWorldState
from mythril_tpu.laser.evm.state.global_state import GlobalState
from mythril_tpu.laser.evm.state.world_state import WorldState
from mythril_tpu.laser.evm.strategy.basic import DepthFirstSearchStrategy
from mythril_tpu.laser.evm.time_handler import time_handler
from mythril_tpu.laser.evm.transaction import (
    ContractCreationTransaction,
    TransactionEndSignal,
    TransactionStartSignal,
    execute_contract_creation,
    execute_message_call,
    transfer_ether,
)
from mythril_tpu.support.opcodes import get_required_stack_elements
from mythril_tpu.smt import symbol_factory

log = logging.getLogger(__name__)

# laser lifecycle hook names -> LaserEVM attribute holding the callbacks
_LIFECYCLE_HOOKS = {
    "add_world_state": "_add_world_state_hooks",
    "execute_state": "_execute_state_hooks",
    "start_sym_exec": "_start_sym_exec_hooks",
    "stop_sym_exec": "_stop_sym_exec_hooks",
    "start_sym_trans": "_start_sym_trans_hooks",
    "stop_sym_trans": "_stop_sym_trans_hooks",
    # fired by the tpu-batch backend after each device round with
    # (bytecode_hex, visited_byte_offsets) — measurement parity for
    # instructions retired on device
    "device_coverage": "_device_coverage_hooks",
}


class SVMError(Exception):
    """An unexpected state in symbolic execution."""


class LaserEVM:
    """Work list + strategy + instruction evaluation + hook surface."""

    def __init__(
        self,
        dynamic_loader=None,
        max_depth=float("inf"),
        execution_timeout=60,
        create_timeout=10,
        strategy=DepthFirstSearchStrategy,
        transaction_count=2,
        requires_statespace=True,
        iprof=None,
        enable_coverage_strategy=False,
        instruction_laser_plugin=None,
    ) -> None:
        self.open_states: List[WorldState] = []
        self.total_states = 0
        self.dynamic_loader = dynamic_loader

        self.work_list: List[GlobalState] = []
        self.strategy = strategy(self.work_list, max_depth)
        self.max_depth = max_depth
        self.transaction_count = transaction_count

        self.execution_timeout = execution_timeout or 0
        self.create_timeout = create_timeout

        self.requires_statespace = requires_statespace
        if requires_statespace:
            self.nodes: Dict[int, Node] = {}
            self.edges: List[Edge] = []

        self.time: Optional[datetime] = None
        self.iprof = iprof

        self.pre_hooks: DefaultDict[str, List[Callable]] = defaultdict(list)
        self.post_hooks: DefaultDict[str, List[Callable]] = defaultdict(list)
        for attribute in _LIFECYCLE_HOOKS.values():
            setattr(self, attribute, [])

        if enable_coverage_strategy:
            from mythril_tpu.laser.evm.plugins.implementations.coverage.coverage_strategy import (
                CoverageStrategy,
            )

            self.strategy = CoverageStrategy(self.strategy, instruction_laser_plugin)

        log.info("LASER EVM initialized with dynamic loader: %s", dynamic_loader)

    def extend_strategy(self, extension, *args) -> None:
        self.strategy = extension(self.strategy, args)

    # -- hook surface ---------------------------------------------------------------

    def register_hooks(self, hook_type: str, hook_dict: Dict[str, List[Callable]]):
        if hook_type == "pre":
            registry = self.pre_hooks
        elif hook_type == "post":
            registry = self.post_hooks
        else:
            raise ValueError(
                "Invalid hook type %s. Must be one of {pre, post}" % hook_type
            )
        for op_code, callbacks in hook_dict.items():
            registry[op_code].extend(callbacks)

    def register_laser_hooks(self, hook_type: str, hook: Callable):
        attribute = _LIFECYCLE_HOOKS.get(hook_type)
        if attribute is None:
            raise ValueError("Invalid hook type %s" % hook_type)
        getattr(self, attribute).append(hook)

    def laser_hook(self, hook_type: str) -> Callable:
        def decorator(func: Callable):
            self.register_laser_hooks(hook_type, func)
            return func

        return decorator

    def pre_hook(self, op_code: str) -> Callable:
        def decorator(func: Callable):
            self.pre_hooks[op_code].append(func)
            return func

        return decorator

    def post_hook(self, op_code: str) -> Callable:
        def decorator(func: Callable):
            self.post_hooks[op_code].append(func)
            return func

        return decorator

    def _execute_pre_hook(self, op_code: str, global_state: GlobalState) -> None:
        for hook in self.pre_hooks.get(op_code, ()):
            hook(global_state)

    def _execute_post_hook(
        self, op_code: str, global_states: List[GlobalState]
    ) -> None:
        for hook in self.post_hooks.get(op_code, ()):
            for global_state in global_states[:]:
                try:
                    hook(global_state)
                except PluginSkipState:
                    global_states.remove(global_state)

    # -- top-level drivers -----------------------------------------------------

    def sym_exec(
        self,
        world_state: WorldState = None,
        target_address: int = None,
        creation_code: str = None,
        contract_name: str = None,
    ) -> None:
        """Symbolically execute either a deployed target (world state +
        address) or creation code from scratch."""
        preconfigured = target_address is not None
        from_scratch = creation_code is not None and contract_name is not None
        if preconfigured == from_scratch:
            raise ValueError("Symbolic execution started with invalid parameters")

        log.debug("Starting LASER execution")
        for hook in self._start_sym_exec_hooks:
            hook()

        time_handler.start_execution(self.execution_timeout)
        self.time = datetime.now()

        if preconfigured:
            self.open_states = [world_state]
            log.info("Starting message call transaction to {}".format(target_address))
            self._execute_transactions(
                symbol_factory.BitVecVal(target_address, 256)
            )
        else:
            log.info("Starting contract creation transaction")
            created_account = execute_contract_creation(
                self, creation_code, contract_name, world_state=world_state
            )
            log.info(
                "Finished contract creation, found {} open states".format(
                    len(self.open_states)
                )
            )
            if not self.open_states:
                log.warning(
                    "No contract was created during the execution of contract creation "
                    "Increase the resources for creation execution (--max-depth or --create-timeout)"
                )
            self._execute_transactions(created_account.address)

        log.info("Finished symbolic execution")
        if self.requires_statespace:
            log.info(
                "%d nodes, %d edges, %d total states",
                len(self.nodes),
                len(self.edges),
                self.total_states,
            )
        if self.iprof is not None:
            log.info("Instruction Statistics:\n%s", self.iprof)
        for hook in self._stop_sym_exec_hooks:
            hook()

    def _execute_transactions(self, address) -> None:
        """Run transaction_count symbolic message-call rounds.

        ``executed_transaction_address`` / ``executed_transaction_rounds``
        are the resume bookkeeping the robustness layer reads: the
        frontier journal records both so a retried job can re-enter here
        (sym_exec_resume) at the round it crashed in."""
        self.executed_transaction_address = address
        if not hasattr(self, "executed_transaction_rounds"):
            self.executed_transaction_rounds = 0
        self.time = datetime.now()
        for round_number in range(self.transaction_count):
            log.info(
                "Starting message call transaction, iteration: {}, {} initial states".format(
                    round_number, len(self.open_states)
                )
            )
            for hook in self._start_sym_trans_hooks:
                hook()
            execute_message_call(self, address)
            # the round is complete BEFORE the stop hooks fire, so a
            # checkpoint hook reading this counter sees the finished
            # round's number
            self.executed_transaction_rounds += 1
            for hook in self._stop_sym_trans_hooks:
                hook()
        # a tpu-batch analysis whose device warmup failed while the host
        # tier ran must not end as if the device had been available
        tpu_marker = self._tpu_strategy_marker()
        if tpu_marker is not None:
            tpu_marker.check_device()

    def sym_exec_resume(
        self, open_states, target_address: int, rounds_done: int = 0
    ) -> None:
        """Resume a message-call analysis from a journaled frontier.

        Runs the REMAINING ``transaction_count - rounds_done`` rounds
        over ``open_states`` against ``target_address`` — the creation
        transaction and the first ``rounds_done`` message-call rounds
        are represented by the frontier itself (robustness/checkpoint
        journals it between rounds). Lifecycle hooks fire exactly as in
        sym_exec so plugins/strategies initialize normally."""
        log.info(
            "Resuming LASER execution from %d open states at round %d",
            len(open_states), rounds_done,
        )
        for hook in self._start_sym_exec_hooks:
            hook()
        time_handler.start_execution(self.execution_timeout)
        self.time = datetime.now()
        self.open_states = list(open_states)
        self.executed_transaction_rounds = rounds_done
        saved_count = self.transaction_count
        self.transaction_count = max(0, saved_count - rounds_done)
        try:
            self._execute_transactions(
                symbol_factory.BitVecVal(target_address, 256)
            )
        finally:
            self.transaction_count = saved_count
        log.info("Finished symbolic execution (resumed)")
        for hook in self._stop_sym_exec_hooks:
            hook()

    # -- the main loop -----------------------------------------------------------

    def _tpu_strategy_marker(self):
        """The TpuBatchStrategy marker in the decorator chain, or None
        (found by class name so the jax-heavy backend module is only
        imported when it will actually run)."""
        strategy = self.strategy
        seen = set()
        while strategy is not None and id(strategy) not in seen:
            seen.add(id(strategy))
            if type(strategy).__name__ == "TpuBatchStrategy":
                return strategy
            strategy = getattr(strategy, "super_strategy", None)
        return None

    def _has_tpu_strategy(self) -> bool:
        return self._tpu_strategy_marker() is not None

    def _timed_out(self, create: bool) -> bool:
        if create and self.create_timeout:
            return self.time + timedelta(seconds=self.create_timeout) <= datetime.now()
        if not create and self.execution_timeout:
            return (
                self.time + timedelta(seconds=self.execution_timeout) <= datetime.now()
            )
        return False

    def exec(self, create=False, track_gas=False) -> Optional[List[GlobalState]]:
        """Drain the strategy: execute, filter, extend.

        tpu-batch runs message-call rounds (including gas-tracked concolic
        replays) through the hybrid host/device loop; creation
        transactions stay on the host path."""
        # IMPORT-FREE marker probe: pulling in the tpu backend just to check
        # the strategy would initialize jax for every pure-host run
        tpu_marker = None if create else self._tpu_strategy_marker()
        if tpu_marker is not None and tpu_marker.engaged():
            from mythril_tpu.laser.tpu.backend import exec_batch

            return exec_batch(self, track_gas=track_gas)

        final_states: List[GlobalState] = []
        for global_state in self.strategy:
            if self._timed_out(create):
                log.debug("Hit a time budget, returning.")
                return final_states + [global_state] if track_gas else None

            # service cancellation (analysis service job_ctx, installed
            # by service/lanes.py): same put-back semantics as a timeout
            # — the selected state returns to the work list, not dropped
            job_ctx = getattr(self, "job_ctx", None)
            if job_ctx is not None and job_ctx.cancelled():
                log.debug("Job cancelled in host loop, returning.")
                if track_gas:
                    return final_states + [global_state]
                self.work_list.insert(0, global_state)
                return None

            # tiered execution: the engagement clock fired mid-phase —
            # put the selected state back and hand the rest of the drain
            # to the hybrid batch backend (below the threshold this loop
            # IS the reference semantics with zero hybrid overhead)
            if tpu_marker is not None and tpu_marker.engaged():
                from mythril_tpu.laser.tpu.backend import exec_batch

                self.work_list.insert(0, global_state)
                batched = exec_batch(self, track_gas=track_gas)
                if track_gas:
                    return final_states + (batched or [])
                return None

            try:
                new_states, op_code = self.execute_state(global_state)
            except NotImplementedError:
                log.debug("Encountered unimplemented instruction")
                continue

            new_states = [
                state
                for state in new_states
                if state.world_state.constraints.is_possible
            ]
            self.manage_cfg(op_code, new_states)
            if new_states:
                self.work_list.extend(new_states)
            elif track_gas:
                final_states.append(global_state)
            self.total_states += len(new_states)

        return final_states if track_gas else None

    # -- single-instruction evaluation ---------------------------------------------

    def execute_state(
        self, global_state: GlobalState
    ) -> Tuple[List[GlobalState], Optional[str]]:
        """Evaluate one instruction on one state; signals become frame
        operations here."""
        for hook in self._execute_state_hooks:
            hook(global_state)

        instructions = global_state.environment.code.instruction_list
        try:
            op_code = instructions[global_state.mstate.pc]["opcode"]
        except IndexError:
            self._add_world_state(global_state)
            return [], None

        if len(global_state.mstate.stack) < get_required_stack_elements(op_code):
            error_msg = (
                "Stack Underflow Exception due to insufficient "
                "stack elements for the address {}".format(
                    instructions[global_state.mstate.pc]["address"]
                )
            )
            new_states = self.handle_vm_exception(global_state, op_code, error_msg)
            self._execute_post_hook(op_code, new_states)
            return new_states, op_code

        try:
            self._execute_pre_hook(op_code, global_state)
        except PluginSkipState:
            self._add_world_state(global_state)
            return [], None

        try:
            new_states = Instruction(
                op_code, self.dynamic_loader, self.iprof
            ).evaluate(global_state)
        except VmException as error:
            new_states = self.handle_vm_exception(global_state, op_code, str(error))
        except TransactionStartSignal as signal:
            return [self._begin_nested_transaction(global_state, signal)], op_code
        except TransactionEndSignal as signal:
            new_states = self._finalize_transaction(global_state, signal, op_code)

        self._execute_post_hook(op_code, new_states)
        return new_states, op_code

    def _begin_nested_transaction(
        self, global_state: GlobalState, signal: TransactionStartSignal
    ) -> GlobalState:
        """CALL/CREATE family: push a frame and descend into the callee."""
        child = signal.transaction.initial_global_state()
        child.transaction_stack = copy(global_state.transaction_stack) + [
            (signal.transaction, global_state)
        ]
        child.node = global_state.node
        child.world_state.constraints = signal.global_state.world_state.constraints
        transfer_ether(
            child,
            signal.transaction.caller,
            signal.transaction.callee_account.address,
            signal.transaction.call_value,
        )
        log.debug("Starting new transaction %s", signal.transaction)
        return child

    def _finalize_transaction(
        self, global_state: GlobalState, signal: TransactionEndSignal, op_code: str
    ) -> List[GlobalState]:
        """STOP/RETURN/REVERT/SELFDESTRUCT: pop the frame; either record an
        open world state (outermost) or resume the caller."""
        transaction, caller_state = signal.global_state.transaction_stack[-1]
        log.debug("Ending transaction %s.", transaction)

        if caller_state is None:
            committed = (
                not isinstance(transaction, ContractCreationTransaction)
                or transaction.return_data
            ) and not signal.revert
            if committed:
                from mythril_tpu.analysis.potential_issues import (
                    check_potential_issues,
                )

                # settlement: the parked findings' solves and the
                # world-state plugins; nests in the round's host_exec
                with obs.phase("settle"):
                    check_potential_issues(global_state)
                    signal.global_state.world_state.node = global_state.node
                    self._add_world_state(signal.global_state)
            return []

        # resuming the caller frame
        self._execute_post_hook(op_code, [signal.global_state])

        from mythril_tpu.laser.evm.plugins.implementations.plugin_annotations import (
            MutationAnnotation,
        )

        call_site_op = caller_state.get_current_instruction()["opcode"]
        if call_site_op in ("DELEGATECALL", "CALLCODE"):
            # mutations inside delegate frames happened to OUR storage
            caller_state.add_annotations(
                list(global_state.get_annotations(MutationAnnotation))
            )

        return self._end_message_call(
            copy(caller_state),
            global_state,
            revert_changes=signal.revert,
            return_data=transaction.return_data,
        )

    def handle_vm_exception(
        self, global_state: GlobalState, op_code: str, error_msg: str
    ) -> List[GlobalState]:
        transaction, caller_state = global_state.transaction_stack.pop()
        if caller_state is None:
            # exceptional halt of the outermost frame: discard all changes
            log.debug("Encountered a VmException, ending path: `%s`", error_msg)
            return []
        self._execute_post_hook(op_code, [global_state])
        return self._end_message_call(
            caller_state, global_state, revert_changes=True, return_data=None
        )

    def _end_message_call(
        self,
        caller_state: GlobalState,
        callee_state: GlobalState,
        revert_changes=False,
        return_data=None,
    ) -> List[GlobalState]:
        """Merge the callee's outcome into the caller and re-evaluate the
        call-site opcode in post mode (writes retval, return data)."""
        caller_state.world_state.constraints += callee_state.world_state.constraints
        call_site_op = caller_state.environment.code.instruction_list[
            caller_state.mstate.pc
        ]["opcode"]

        caller_state.last_return_data = return_data
        if not revert_changes:
            caller_state.world_state = copy(callee_state.world_state)
            caller_state.environment.active_account = callee_state.accounts[
                caller_state.environment.active_account.address.value
            ]
            if isinstance(
                callee_state.current_transaction, ContractCreationTransaction
            ):
                caller_state.mstate.min_gas_used += callee_state.mstate.min_gas_used
                caller_state.mstate.max_gas_used += callee_state.mstate.max_gas_used

        resumed = Instruction(call_site_op, self.dynamic_loader, self.iprof).evaluate(
            caller_state, True
        )
        for state in resumed:
            state.node = callee_state.node
        return resumed

    # -- world-state & CFG bookkeeping ------------------------------------------

    def _add_world_state(self, global_state: GlobalState):
        """Record an open world state (plugins may veto)."""
        for hook in self._add_world_state_hooks:
            try:
                hook(global_state)
            except PluginSkipWorldState:
                return
        self.open_states.append(global_state.world_state)

    def manage_cfg(self, opcode: Optional[str], new_states: List[GlobalState]) -> None:
        if opcode == "JUMP":
            assert len(new_states) <= 1
            for state in new_states:
                self._new_node_state(state)
        elif opcode == "JUMPI":
            assert len(new_states) <= 2
            for state in new_states:
                self._new_node_state(
                    state, JumpType.CONDITIONAL, state.world_state.constraints[-1]
                )
        elif opcode in ("SLOAD", "SSTORE") and len(new_states) > 1:
            for state in new_states:
                self._new_node_state(
                    state, JumpType.CONDITIONAL, state.world_state.constraints[-1]
                )
        elif opcode == "RETURN":
            for state in new_states:
                self._new_node_state(state, JumpType.RETURN)
        for state in new_states:
            state.node.states.append(state)

    def _new_node_state(
        self, state: GlobalState, edge_type=JumpType.UNCONDITIONAL, condition=None
    ) -> None:
        new_node = Node(state.environment.active_account.contract_name)
        old_node = state.node
        state.node = new_node
        new_node.constraints = state.world_state.constraints
        if self.requires_statespace:
            self.nodes[new_node.uid] = new_node
            self.edges.append(
                Edge(
                    old_node.uid, new_node.uid, edge_type=edge_type, condition=condition
                )
            )

        if edge_type == JumpType.RETURN:
            new_node.flags |= NodeFlags.CALL_RETURN
        elif edge_type == JumpType.CALL:
            try:
                if "retval" in str(state.mstate.stack[-1]):
                    new_node.flags |= NodeFlags.CALL_RETURN
                else:
                    new_node.flags |= NodeFlags.FUNC_ENTRY
            except StackUnderflowException:
                new_node.flags |= NodeFlags.FUNC_ENTRY

        instruction_list = state.environment.code.instruction_list
        if state.mstate.pc >= len(instruction_list):
            # fall-through past the last instruction: the path halts on its
            # next step; no CFG node naming applies
            return
        address = instruction_list[state.mstate.pc]["address"]
        environment = state.environment
        disassembly = environment.code
        if isinstance(
            state.world_state.transaction_sequence[-1], ContractCreationTransaction
        ):
            environment.active_function_name = "constructor"
        elif address in disassembly.address_to_function_name:
            environment.active_function_name = disassembly.address_to_function_name[
                address
            ]
            new_node.flags |= NodeFlags.FUNC_ENTRY
            log.debug(
                "- Entering function %s:%s",
                environment.active_account.contract_name,
                new_node.function_name,
            )
        elif address == 0:
            environment.active_function_name = "fallback"

        new_node.function_name = environment.active_function_name

