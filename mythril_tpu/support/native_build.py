"""Build/load the native C++ engine (keccak + CDCL SAT) via ctypes.

The shared library is compiled on first use with g++ (no pybind11 — plain C
ABI) and cached under mythril_tpu/_build/, named by a hash of the source:
only a library built from the native.cpp of this checkout ever loads, never
one a copied tree carried from another machine or revision. If no compiler
is available the callers fall back to the pure-Python implementations.
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_PKG_DIR, "csrc", "native.cpp")
_BUILD_DIR = os.path.join(_PKG_DIR, "_build")


def library_path() -> str:
    """The built library for the current native.cpp."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, "_mythril_native-%s.so" % digest)


def _build(so: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process temporary: concurrent first builds must not interleave
    tmp = "%s.%d.tmp" % (so, os.getpid())
    cmd = ["g++", "-O3", "-std=c++14", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, so)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        log.warning("native build failed (%s); using pure-python fallbacks", e)
        return False


def load_native_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            so = library_path()
            if not os.path.exists(so) and not _build(so):
                return None
            _lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load native lib: %s", e)
            _lib = None
        return _lib
