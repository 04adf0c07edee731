"""Device: 1 - busy/window of the profiler trace's traced part."""

from benchmark.lib.readers import device_idle_share


def read(result):
    return device_idle_share(result)
