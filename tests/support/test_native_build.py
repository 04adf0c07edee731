"""The native library is named by a hash of its source, so a tree copied
from elsewhere can carry a stale build that simply never loads."""

import os

import pytest

from mythril_tpu.support import native_build


def test_library_path_follows_the_source_contents(tmp_path, monkeypatch):
    src = tmp_path / "native.cpp"
    src.write_text("int version = 1;\n")
    monkeypatch.setattr(native_build, "_SRC", str(src))
    first = native_build.library_path()
    assert native_build.library_path() == first
    src.write_text("int version = 2;\n")
    second = native_build.library_path()
    assert second != first
    assert os.path.dirname(first) == os.path.dirname(second) == native_build._BUILD_DIR


def test_loaded_library_is_the_one_built_from_this_source():
    lib = native_build.load_native_lib()
    if lib is None:
        pytest.skip("no C++ compiler: callers use the pure-Python fallbacks")
    assert lib._name == native_build.library_path()
