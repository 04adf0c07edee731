"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(result):
    return result.memory_peak_bytes or None
