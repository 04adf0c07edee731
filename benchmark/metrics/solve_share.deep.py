"""Solver (solver_cache.py, solver_jax.py): phase ``solve`` over the window."""

from benchmark.lib.readers import phase_share


def read(result):
    return phase_share(result, "solve")
