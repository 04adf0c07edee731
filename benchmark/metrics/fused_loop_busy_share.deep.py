"""Kernels: device time of the fused-loop executable (``jit__fused_impl``)
over the traced window."""

from benchmark.lib.readers import program_share


def read(result):
    return program_share(result)
