"""Transport (laser/tpu/transfer.py, robustness/retry.py): phases
``transfer_up`` and ``transfer_down`` over the window."""

from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "transfer_up", "transfer_down")
