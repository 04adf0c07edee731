"""Steady-state window meter, benchmark protocol v1.

A copy of the program's protocol-v1 meter (mythril_tpu/support/
benchmeter.py), kept here so that no change to the program can change
how the benchmark counts. The window opens at the start of a
message-call transaction (the LaserEVM ``start_sym_trans`` hook; the
k-th one with ``open_at_tx=k``) and closes after detection and witness
solving (``fire_lasers``), so the post-pass stays in the denominator.
States counted are host ``total_states`` plus the instructions the
tpu-batch strategy retired on the device.
"""

import time
from typing import Callable, Optional


def device_steps(laser) -> int:
    """Device-retired instructions of the TpuBatchStrategy anywhere in
    the strategy decorator chain, found by attribute."""
    strategy, seen = laser.strategy, set()
    while strategy is not None and id(strategy) not in seen:
        seen.add(id(strategy))
        retired = getattr(strategy, "device_steps_retired", None)
        if retired is not None:
            return int(retired)
        strategy = getattr(strategy, "super_strategy", None)
    return 0


class SteadyStateMeter:
    def __init__(self, open_at_tx: int = 1,
                 on_open: Optional[Callable[[object], None]] = None):
        self.open_at_tx = open_at_tx
        self.on_open = on_open
        self.laser = None
        self.tx_starts = []  # time.monotonic() of each transaction start
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.states_open = 0
        self.device_steps_open = 0
        self.states = 0
        self.device_steps = 0

    def install(self, laser) -> None:
        """Fits SymExecWrapper's ``pre_exec_hook``."""
        self.laser = laser
        laser.register_laser_hooks("start_sym_trans", self._tx_start)

    def _tx_start(self) -> None:
        self.tx_starts.append(time.monotonic())
        if self.t_open is None and len(self.tx_starts) == self.open_at_tx:
            self.states_open = self.laser.total_states
            self.device_steps_open = device_steps(self.laser)
            self.t_open = time.monotonic()
            if self.on_open is not None:
                self.on_open(self.laser)

    def close(self) -> None:
        """Call after fire_lasers. A run whose window never opened has
        no window (``t_open`` stays None)."""
        if self.t_open is None or self.t_close is not None:
            return
        self.t_close = time.monotonic()
        self.device_steps = device_steps(self.laser) - self.device_steps_open
        self.states = (self.laser.total_states - self.states_open) + self.device_steps

    @property
    def wall(self) -> float:
        return self.t_close - self.t_open

    @property
    def states_per_s(self) -> float:
        return self.states / self.wall
