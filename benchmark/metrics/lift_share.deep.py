"""Bridge (laser/tpu/bridge.py): phase ``lift`` over the window."""

from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "lift")
