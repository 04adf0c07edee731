"""Kernels: the blocking device warmup's seconds (``myth_warmup_s``,
summed over its stages) over ``setup_s``."""

from benchmark.lib.program_counts import registry_series


def read(result):
    from mythril_tpu import obs

    if obs.TRACER.dropped > 0 or not result.setup_s:
        return None
    stages = registry_series(obs.REGISTRY.snapshot(), "myth_warmup_s", "stage")
    total = sum(stages.values())
    return total / result.setup_s if total > 0 else None
