"""Symbolic VM and round loop: phase ``host_exec`` over the window."""

from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "host_exec")
