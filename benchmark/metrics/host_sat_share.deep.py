"""Solver: phase ``host_sat`` (every native SAT solve on the host,
``IncrementalCore.solve_checked``) over the window. It nests in
host_exec, settle, triage, solve and lift, so it is a part of their
shares, not an addend beside them."""

from benchmark.lib.program_counts import span_share


def read(result):
    return span_share(result, "host_sat")
