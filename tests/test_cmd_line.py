"""CLI end-to-end: shell out to `myth` and grep stdout (reference surface:
tests/cmd_line_test.py)."""

import json
import os
import subprocess
import sys

import pytest

from mythril_tpu.disassembler.asm import assemble

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MYTH = os.path.join(REPO, "myth")

# CALLVALUE; SSTORE; CALLER SELFDESTRUCT — an unprotected-selfdestruct target
RUNTIME = assemble("CALLVALUE\nPUSH1 0x00\nSSTORE\nCALLER\nSELFDESTRUCT").hex()


def creation_of(runtime_hex: str) -> str:
    n = len(runtime_hex) // 2
    src = (
        f"PUSH2 {n}\nPUSH2 :code\nPUSH1 0x00\nCODECOPY\nPUSH2 {n}\n"
        "PUSH1 0x00\nRETURN\ncode:"
    )
    return assemble(src).hex() + runtime_hex


def myth(*argv, timeout=900):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, MYTH, *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=env,
    )
    return proc


def test_version():
    out = myth("version").stdout
    assert "Mythril-TPU version v" in out


def test_version_json():
    out = myth("version", "-o", "json").stdout
    assert json.loads(out)["version_str"].startswith("v")


def test_list_detectors():
    out = myth("list-detectors").stdout
    assert "EtherThief" in out and "TxOrigin" in out


def test_function_to_hash():
    out = myth("function-to-hash", "transfer(address,uint256)").stdout
    assert out.strip() == "0xa9059cbb"


def test_hash_to_address():
    out = myth(
        "hash-to-address",
        "0x0000000000000000000000001234567890123456789012345678901234567890",
    ).stdout
    assert out.strip() == "0x1234567890123456789012345678901234567890"


def test_disassemble_code():
    out = myth("disassemble", "-c", "0x6001600101", "--bin-runtime").stdout
    assert "PUSH1" in out and "ADD" in out


def test_no_input_error_json():
    proc = myth("analyze", "-o", "json")
    data = json.loads(proc.stdout)
    assert data["success"] is False
    assert "No input bytecode" in data["error"]
    assert proc.returncode == 1


def test_help_lists_all_subcommands():
    out = myth("--help").stdout
    for sub in ("analyze", "disassemble", "pro", "leveldb-search", "truffle",
                "read-storage", "list-detectors"):
        assert sub in out, sub


def test_leveldb_search_missing_db_errors_cleanly():
    proc = myth(
        "leveldb-search", "deadbeef", "--leveldb-dir", "/nonexistent/chaindata"
    )
    assert proc.returncode == 1
    assert "Could not open LevelDB" in proc.stdout + proc.stderr


def test_leveldb_search_on_disk_db(tmp_path):
    """End-to-end: author a real-format LevelDB with code-bearing state
    and search it from the CLI through the pure-Python reader."""
    from mythril_tpu.ethereum.interface.leveldb.pyleveldb import PyLevelDBWriter
    from tests.support.test_leveldb import populate_chaindata, CONTRACT_ADDR

    writer = PyLevelDBWriter(str(tmp_path / "chaindata"))
    populate_chaindata(writer)
    writer.close()
    proc = myth(
        "leveldb-search", "60016001", "--leveldb-dir",
        str(tmp_path / "chaindata"),
    )
    assert "0x" + CONTRACT_ADDR.hex() in proc.stdout


def test_truffle_analyzes_build_artifacts(tmp_path):
    import json as _json

    runtime = RUNTIME
    creation = creation_of(runtime)
    build = tmp_path / "build" / "contracts"
    build.mkdir(parents=True)
    (build / "Killable.json").write_text(
        _json.dumps(
            {
                "contractName": "Killable",
                "bytecode": "0x" + creation,
                "deployedBytecode": "0x" + runtime,
            }
        )
    )
    # an abstract contract without runtime code must be skipped
    (build / "IEmpty.json").write_text(
        _json.dumps(
            {"contractName": "IEmpty", "bytecode": "0x", "deployedBytecode": "0x"}
        )
    )
    # runtime-only artifact (no creation code): must analyze through the
    # message-call path with the placeholder address, not crash
    (build / "RuntimeOnly.json").write_text(
        _json.dumps(
            {
                "contractName": "RuntimeOnly",
                "bytecode": "0x",
                "deployedBytecode": "0x" + runtime,
            }
        )
    )
    proc = myth(
        "truffle", "--project-dir", str(tmp_path),
        "-t", "1", "--execution-timeout", "300",
    )
    assert "SWC ID: 106" in proc.stdout
    assert "RuntimeOnly" in proc.stdout


def test_analyze_bytecode_text():
    proc = myth(
        "analyze",
        "-c", creation_of(RUNTIME),
        "--no-onchain-data", "-t", "1",
        "--execution-timeout", "300",
    )
    assert "SWC ID: 106" in proc.stdout


ORIGIN_O = "/root/reference/tests/testdata/inputs/origin.sol.o"


@pytest.mark.skipif(not os.path.exists(ORIGIN_O), reason="corpus not mounted")
def test_analyze_tpu_batch_default_config_terminates():
    """The flagship mode with the PRODUCT default batch config must finish
    from a cold CLI (VERDICT r3: two 9-minute non-terminating runs).
    Warmup compiles on a background thread while host rounds make
    progress, so wall time is bounded by the host path + --execution-
    timeout even if the XLA compile is slow."""
    proc = myth(
        "analyze",
        "-f", ORIGIN_O,
        "--bin-runtime", "-t", "2",
        "--strategy", "tpu-batch",
        "--execution-timeout", "120",
        timeout=420,
    )
    assert "SWC ID: 115" in proc.stdout


def test_analyze_bytecode_json_tpu_batch():
    proc = myth(
        "analyze",
        "-c", creation_of(RUNTIME),
        "--no-onchain-data", "-t", "1",
        "--strategy", "tpu-batch",
        "--lanes", "16",
        "--execution-timeout", "480",
        "-o", "json",
    )
    data = json.loads(proc.stdout)
    assert data["success"] is True
    swcs = {issue["swc-id"] for issue in data["issues"]}
    assert "106" in swcs


def _analyze_with_failed_warmup(monkeypatch, capsys, warmup_async):
    """Run `myth analyze --strategy tpu-batch` in process on a config no
    other test warms, with fresh warmup bookkeeping; return the exit
    code and the JSON report."""
    from mythril_tpu.interfaces import cli
    from mythril_tpu.laser.tpu import backend

    monkeypatch.setattr(
        backend, "DEFAULT_BATCH_CFG", backend.DEFAULT_BATCH_CFG._replace(lanes=24)
    )
    monkeypatch.setattr(backend, "_warmup_events", {})
    monkeypatch.setattr(backend, "_warmup_done", set())
    monkeypatch.setattr(backend, "_warmup_errors", {})
    monkeypatch.setattr(backend, "WARMUP_ASYNC", warmup_async)
    with pytest.raises(SystemExit) as exit_info:
        cli.main([
            "analyze", "-c", creation_of(RUNTIME), "--no-onchain-data",
            "--strategy", "tpu-batch", "-t", "1", "-o", "json",
        ])
    report = json.loads(capsys.readouterr().out)
    # the aborted analysis left no findings behind for the next one
    from mythril_tpu.analysis.security import retrieve_callback_issues

    assert retrieve_callback_issues() == []
    return exit_info.value.code, report


@pytest.mark.parametrize("backend_down", [False, True])
@pytest.mark.parametrize("warmup_async", [False, True])
def test_tpu_batch_warmup_failure_exits_nonzero(
    monkeypatch, capsys, warmup_async, backend_down
):
    """A tpu-batch analysis whose device kernels fail to compile must not
    finish on the host as if nothing happened: the CLI exits nonzero
    and says why, whether the warmup blocked or ran in the background,
    and also when jax itself can no longer start its backend."""
    import jax

    from mythril_tpu.laser.tpu import backend

    def broken_compile(cfg, want_stats):
        if backend_down:
            monkeypatch.setattr(jax, "devices", _backend_down)
        raise RuntimeError("XLA compile failed (forced by the test)")

    def _backend_down(*args, **kwargs):
        raise RuntimeError("Unable to initialize backend 'tpu' (forced)")

    monkeypatch.setattr(backend, "_warm_kernels", broken_compile)
    code, report = _analyze_with_failed_warmup(monkeypatch, capsys, warmup_async)
    assert code == 1
    assert report["success"] is False
    assert "device warmup failed" in report["error"]
    assert "forced by the test" in report["error"]


def test_tpu_batch_cpu_fallback_on_a_chip_host_exits_nonzero(monkeypatch, capsys):
    """With JAX_PLATFORMS unset, jax falls back to its CPU backend when
    the TPU will not start; on a host with a chip that fallback must end
    the analysis instead of running its device rounds on the CPU."""
    from mythril_tpu.laser.tpu import compile_cache_dir
    from mythril_tpu.support import accelerators

    # jax already runs on the CPU in this process; pretend a chip is
    # there, keeping the compile cache where the suite put it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(accelerators, "host_chip_count", lambda: 1)
    code, report = _analyze_with_failed_warmup(monkeypatch, capsys, False)
    assert code == 1
    assert report["success"] is False
    assert "fell back to its CPU backend" in report["error"]
