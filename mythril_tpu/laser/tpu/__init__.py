"""TPU batch engine: vmapped symbolic EVM over structure-of-arrays state.

This package is the TPU-native core that replaces the reference's
per-object interpreter loop (mythril/laser/ethereum/svm.py:220 exec / one
GlobalState at a time) with a batched, jittable step over thousands of
path-lanes packed SoA in HBM:

- words.py      — 256-bit EVM word arithmetic as 16x16-bit digit limbs (u32 lanes)
- batch.py      — the SoA state batch (pytree) + code bank
- symtape.py    — per-lane symbolic term tapes (device expression DAG)
- engine.py     — the fused one-instruction step kernel + JUMPI lane forking
- backend.py    — host driver bridging the batch world to the LaserEVM API
- bridge.py     — term-tape lift/pack between host SMT layer and device
- solver_jax.py — batched CNF feasibility kernel
- transfer.py   — single-buffer host<->device plane transport
- mesh.py       — sharded multi-device lockstep rounds + rebalance
"""

import os
import sys


def cpu_fingerprint() -> str:
    """Short stable id of this host's CPU feature set (cache keying)."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as fh:
            flags = next(
                (line for line in fh if line.startswith("flags")), ""
            )
    except OSError:
        import platform

        flags = platform.processor() or platform.machine()
    return hashlib.sha1(flags.encode()).hexdigest()[:12]


# root of the checkout this package runs from
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def compile_cache_dir() -> str:
    """Where the persistent XLA compile cache lives.

    ``JAX_COMPILATION_CACHE_DIR`` when it is set; otherwise one fixed
    directory inside the checkout (the path is part of the cache key, so
    it must not move between runs). On the CPU backend the directory
    carries the host's CPU fingerprint: XLA:CPU entries bake the
    compiling host's ISA features into the executable but the cache key
    does not, and reusing them on different silicon logs SIGILL warnings
    and aborts interpreter teardown."""
    explicit = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if explicit:
        return explicit
    from mythril_tpu.support.accelerators import runs_on_cpu

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if runs_on_cpu():
        path += "-cpu-" + cpu_fingerprint()
    return path


def ensure_compile_cache() -> None:
    """Point jax at the persistent compile cache (compile_cache_dir).

    Every entry point that can initialize jax for device work (CLI,
    bench, library warmup) funnels through here so repeat invocations
    pay each compile once. Safe to call any number of times. Does NOT
    import jax: the env vars cover a later import, and the config path
    covers a process that imported jax already, so CLI commands that
    never touch a device keep their fast startup.
    """
    cache_dir = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # default floor is 1s of compile time; these kernels always clear
    # it, but pin a low floor so smaller helpers cache too
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    jax = sys.modules.get("jax")
    if jax is not None:  # env vars alone are too late by then
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
        )
