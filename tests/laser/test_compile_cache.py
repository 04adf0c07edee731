"""Where the persistent XLA compile cache lives (ensure_compile_cache).

The directory must be one a caller can place from outside, and fixed
otherwise: the path is part of the cache key, so a directory that moves
between runs never hits. These tests do not import jax, and check that
ensure_compile_cache does not either.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROBE = """
import json, os, sys
from mythril_tpu.laser.tpu import cpu_fingerprint, ensure_compile_cache
ensure_compile_cache()
print(json.dumps({
    "dir": os.environ["JAX_COMPILATION_CACHE_DIR"],
    "fingerprint": cpu_fingerprint(),
    "jax_imported": "jax" in sys.modules,
}))
"""


def probe(**env_overrides):
    env = {
        k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", PROBE],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
        check=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_explicit_cache_dir_is_used_as_given(tmp_path):
    placed = str(tmp_path / "placed-cache")
    result = probe(JAX_COMPILATION_CACHE_DIR=placed, JAX_PLATFORMS="tpu")
    assert result["dir"] == placed
    assert not result["jax_imported"]


def test_default_cache_dir_is_fixed_inside_the_checkout():
    tpu = probe(JAX_PLATFORMS="tpu")
    assert tpu["dir"] == os.path.join(REPO, ".jax_cache")
    assert not tpu["jax_imported"]
    cpu = probe(JAX_PLATFORMS="cpu")
    assert cpu["dir"] == os.path.join(
        REPO, ".jax_cache-cpu-" + cpu["fingerprint"]
    )
    # no pid, time or temporary name: a second process lands on the same path
    assert probe(JAX_PLATFORMS="cpu")["dir"] == cpu["dir"]
    assert not cpu["jax_imported"]
