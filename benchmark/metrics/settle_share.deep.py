"""Symbolic VM and round loop: phase ``settle`` (the outermost commit at
transaction end: parked findings' solves and world-state plugins) over
the window. It nests in host_exec."""

from benchmark.lib.program_counts import span_share


def read(result):
    return span_share(result, "settle")
