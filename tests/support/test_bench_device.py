"""bench.py measures the TPU or nothing: a run that finds no TPU fails,
and only an explicit JAX_PLATFORMS=cpu runs it on the CPU, stamped so."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as module

    return module


def test_bench_without_a_tpu_fails_unless_cpu_is_asked_for(bench, monkeypatch):
    # this process runs jax on the CPU (tests/conftest.py)
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit, match="no TPU found"):
        bench._device_info()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    device = bench._device_info()
    assert device["platform"] == "cpu"
    assert device["device_count"] >= 1 and device["device_kind"]


def test_bench_watchdog_parent_stays_off_jax():
    """The parent only supervises; the chip belongs to its child."""
    import subprocess

    probe = (
        "import sys; sys.path.insert(0, %r); import bench; "
        "print('jax' in sys.modules)" % REPO
    )
    out = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True,
        timeout=120, check=True,
    ).stdout
    assert out.strip() == "False"
