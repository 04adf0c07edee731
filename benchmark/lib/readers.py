"""Shared arithmetic of the per-layer metric readers.

A reader returns None where it finds nothing to read, and the harness
then leaves the metric out of the line: a share is never reported as 0
for want of a reading.
"""

from typing import Optional


def phase_share(result, *phases: str) -> Optional[float]:
    """Summed window seconds of the phases over the window's wall. The
    round loop's phases do not nest, so the sum is their self time."""
    seconds = sum(result.phase_s.get(p, 0.0) for p in phases)
    if not result.window_s or seconds <= 0:
        return None
    return seconds / result.window_s


def device_idle_share(result) -> Optional[float]:
    t = result.trace
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]


def program_share(result) -> Optional[float]:
    t = result.trace
    if not t or not t["devices"] or t["program_s"] <= 0 or t["window_s"] <= 0:
        return None
    return t["program_s"] / t["window_s"]
