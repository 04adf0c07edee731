import os

# Tests run on the CPU backend, on a virtual 8-device mesh, so the
# multi-chip sharding logic is exercised without TPU hardware. Forced,
# not defaulted: the suite must never take a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Shrink the batched device solver's canonical kernel shapes: full-size
# (4096 vars x 16k clauses) takes minutes to XLA-compile on the CPU mesh
# and would eat per-test execution budgets. Small shapes still exercise
# the whole pipeline; EVM-sized instances just fall back to the host CDCL.
from mythril_tpu.laser.tpu import solver_jax as _solver_jax  # noqa: E402

_solver_jax.MAX_VARS = 512
_solver_jax.MAX_CLAUSES = 2048

# Production warms up asynchronously (host rounds overlap XLA compile);
# tests assert device participation deterministically, so the strategy
# constructor must block until the kernels are compiled.
from mythril_tpu.laser.tpu import backend as _backend  # noqa: E402

_backend.WARMUP_ASYNC = False

# The solver verdict memo (laser/tpu/solver_cache.GLOBAL) is keyed by
# interned term uids and alpha-digests, both stable process-wide — a
# verdict recorded by one test would answer a lookup in the next and
# mask real solver behaviour. Reset it around every test.
import pytest  # noqa: E402

from mythril_tpu.laser.tpu import solver_cache as _solver_cache  # noqa: E402


@pytest.fixture
def generous_solver_budget(monkeypatch):
    """Witness solves (analysis/solver.get_model) get a budget that a
    heavily loaded machine does not miss. Tests that assert findings use
    it: a solve cut short by the default 10 s budget drops the finding.
    get_model also caps a solve at the analysis's remaining execution
    budget, so those tests give their analyses room too."""
    from mythril_tpu.analysis.analysis_args import analysis_args

    monkeypatch.setitem(analysis_args._params, "solver_timeout", 600_000)


@pytest.fixture(autouse=True)
def _fresh_solver_cache():
    _solver_cache.reset_for_tests()
    yield
    _solver_cache.reset_for_tests()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long symbolic-execution runs excluded from the tier-1 "
        "gate (pytest -m 'not slow')",
    )
