"""What a driver hands back to the harness, and what metric readers read."""

import re
from typing import Dict, List, Optional


class Result:
    def __init__(self):
        self.setup_s: Optional[float] = None
        self.e2e: Dict[str, float] = {}
        self.analyses: List = []  # benchmark.lib.check.Analysis, one per analysis
        self.attempted = 0
        self.failed = 0
        self.window_s: Optional[float] = None  # wall of the measured window
        self.phase_s: Dict[str, float] = {}  # myth_round_phase_s summed over the window
        self.notes: Dict = {}  # printed on an earlier line
        self.trace: Optional[Dict] = None  # benchmark.lib.trace.reduce of the traced part
        self.memory_peak_bytes: Optional[int] = None
        self._held: List = []

    def hold(self, obj) -> None:
        """Keep program state alive until the memory peak was read."""
        self._held.append(obj)

    def release(self) -> None:
        self._held.clear()


def phase_delta(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v - before.get(k, 0.0) > 0}


_PHASE_SUM = re.compile(r'^myth_round_phase_s_sum\{phase="([a-z_]+)"\}$')


def phase_seconds() -> Dict[str, float]:
    """Summed ``myth_round_phase_s`` per phase, as the program counts it
    (its Prometheus view, so a reading needs only the metric's name)."""
    from mythril_tpu import obs

    out = {}
    for key, value in obs.REGISTRY.snapshot().items():
        m = _PHASE_SUM.match(key)
        if m:
            out[m.group(1)] = float(value)
    return out
