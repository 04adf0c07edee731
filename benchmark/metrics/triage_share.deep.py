"""Round loop: phase ``triage`` over the window."""

from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "triage")
