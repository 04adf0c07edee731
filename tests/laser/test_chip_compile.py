"""Compile the main-path kernels for a described TPU v5e, without a chip.

The CPU suite never compiles for the TPU, so a kernel the chip's compiler
refuses (a tiling it cannot lay out, more memory than the device holds,
a collective it cannot partition) would otherwise first show up on the
chip. These tests lower each kernel of the device path at its production
shapes against a described ``v5e:2x2`` topology and compile it with the
installed TPU compiler. Nothing runs: the arguments are shapes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file. Keep every such compile in this one file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from mythril_tpu.laser.tpu import backend, engine, megakernel, solver_jax
from mythril_tpu.laser.tpu.batch import StateBatch, batch_shapes, default_env, make_code_bank
from mythril_tpu.laser.tpu.keccak_tpu import keccak256_batch
from mythril_tpu.laser.tpu.solver_cache import SolverCache

# one v5e chip holds 16 GB of HBM
V5E_HBM_BYTES = 16 * 10**9

# the widest batch ever timed on a chip (raw step kernel, 8192 lanes)
WIDE_LANES = 8192

# production caps of the batched device solver; tests/conftest.py shrinks
# the module globals for the CPU suite, so the real numbers are spelled out
PROD_MAX_VARS = 4096
PROD_MAX_CLAUSES = 1 << 14


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding),
        tree,
    )


def _batch_shapes(cfg, sharding) -> StateBatch:
    return StateBatch(
        **{
            field: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for field, (shape, dtype) in batch_shapes(cfg).items()
        }
    )


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    need = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert need < V5E_HBM_BYTES, f"{need} bytes do not fit one v5e: {mem}"
    return need


def _fused_args(cfg, sharding):
    cb = make_code_bank([b"\x00"], cfg.code_len, host_ops=(), freeze_errors=True)
    pool = SolverCache().build_inloop_pool()
    return (
        _shapes(cb, sharding),
        _shapes(default_env(), sharding),
        _batch_shapes(cfg, sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
        _shapes(pool, sharding),
    )


@pytest.mark.parametrize("lanes", [backend.DEFAULT_BATCH_CFG.lanes, WIDE_LANES])
def test_fused_megakernel_compiles_for_v5e(one_chip, lanes):
    cfg = backend.DEFAULT_BATCH_CFG._replace(lanes=lanes)
    lowered = megakernel._fused_impl.lower(
        *_fused_args(cfg, one_chip),
        steps_per_round=backend.FUSED_STEPS_PER_ROUND,
        with_stats=False,
        with_solve=True,
    )
    _fits(lowered.compile())


def test_solver_top_bucket_compiles_for_v5e(one_chip):
    I, V, C = solver_jax.MAX_BATCH, PROD_MAX_VARS, PROD_MAX_CLAUSES
    args = (
        jax.ShapeDtypeStruct((I, C, 3), jnp.int32, sharding=one_chip),
        _shapes(jax.random.PRNGKey(0), one_chip),
        jax.ShapeDtypeStruct((I,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((I, V), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((I, V), jnp.int8, sharding=one_chip),
    )
    kernel = jax.jit(solver_jax._solve_kernel, static_argnums=(5, 6))
    _fits(kernel.lower(*args, V, backend.SOLVE_FLIPS).compile())


def test_keccak_batch_compiles_for_v5e(one_chip):
    lanes = backend.DEFAULT_BATCH_CFG.lanes
    lowered = keccak256_batch.lower(
        jax.ShapeDtypeStruct((lanes, engine.SHA_CAP), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
    )
    _fits(lowered.compile())


def test_fused_mesh_kernel_compiles_for_v5e_2x2(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("paths",))
    lanes_spec = NamedSharding(mesh, P("paths"))
    repl = NamedSharding(mesh, P())
    cb, env, _st, max_rounds, pool = _fused_args(backend.DEFAULT_BATCH_CFG, repl)
    st = _batch_shapes(backend.DEFAULT_BATCH_CFG, lanes_spec)
    kernel = megakernel._mesh_kernel(
        mesh, backend.FUSED_STEPS_PER_ROUND, False, True
    )
    compiled = kernel.lower(cb, env, st, max_rounds, pool).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "all-to-all" in hlo or "all-gather" in hlo, "no collective in the mesh kernel"
