"""Which backend jax will run on, read from the environment and the
host's device nodes without importing jax."""

import pytest

from mythril_tpu.support import accelerators


@pytest.mark.parametrize(
    "platforms, chips, requested, on_cpu",
    [
        ("cpu", 1, True, True),
        (" cpu ,tpu", 1, True, True),
        ("tpu", 0, False, False),
        ("tpu,cpu", 1, False, False),
        ("", 0, False, True),
        ("", 1, False, False),
        (" ", 1, False, False),
    ],
)
def test_cpu_requested_and_runs_on_cpu(monkeypatch, platforms, chips, requested, on_cpu):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(accelerators, "host_chip_count", lambda: chips)
    assert accelerators.cpu_requested() is requested
    assert accelerators.runs_on_cpu() is on_cpu
