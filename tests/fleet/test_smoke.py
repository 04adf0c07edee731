"""2-worker in-proc fleet smoke (the check.sh fleet gate).

One gateway over two stubbed-pipeline services sharing a durable
store directory. Asserts the two fleet acceptance behaviors end to
end, without subprocesses or devices:

  * a watch stream delivers an issue event BEFORE the job completes;
  * a duplicate submission after the owning worker dies fails over and
    warm-hits the OTHER worker's cache through the shared store, with
    an identical report.
"""

import time

import pytest

from mythril_tpu.fleet.gateway import Gateway
from mythril_tpu.fleet.qos import AdmissionController
from mythril_tpu.fleet.store import DurableResultCache
from mythril_tpu.fleet.worker import LocalWorker

from tests.fleet.stubs import FleetStubService

CODE = "6001600155"


@pytest.fixture
def fleet(tmp_path):
    store_dir = str(tmp_path / "store")
    caches = [
        DurableResultCache(store_dir, refresh_interval_s=0.0)
        for _ in range(2)
    ]
    services = [
        FleetStubService(workers=1, queue_size=8, cache=cache)
        for cache in caches
    ]
    gw = Gateway(
        [LocalWorker("w%d" % i, s) for i, s in enumerate(services)],
        admission=AdmissionController(base_rate_per_s=1000.0, burst=1000.0),
    )
    yield gw, services, caches
    for service in services:
        service.release.set()
        service.shutdown(wait=True, timeout=10)
    for cache in caches:
        cache.close()


def test_stream_then_cross_worker_warm_hit(fleet):
    gw, services, caches = fleet
    for service in services:
        service.release.clear()

    # --- streamed issue event before job completion ---
    resp = gw.handle({"op": "submit", "code": CODE, "name": "Smoke"})
    assert resp["ok"]
    gid = resp["job_id"]
    stream = gw.handle_stream({"op": "watch", "job_id": gid})
    first = next(stream)
    assert first["event"] == "issue"
    assert first["job_id"] == gid
    status = gw.handle({"op": "status", "job_id": gid})
    assert status["state"] == "running"  # the stream beat completion
    for service in services:
        service.release.set()
    events = [first] + list(stream)
    assert events[-1]["event"] == "end" and events[-1]["state"] == "done"

    cold = gw.handle({"op": "result", "job_id": gid, "timeout": 10})
    assert cold["ok"] and not cold["cache_hit"]

    # --- worker death + duplicate: cross-worker warm hit ---
    owner = resp["worker"]
    owner_idx = int(owner[1:])
    gw.mark_dead(owner)
    dup = gw.handle({"op": "submit", "code": CODE, "name": "Smoke"})
    assert dup["ok"] and dup["worker"] != owner
    warm = gw.handle({"op": "result", "job_id": dup["job_id"], "timeout": 10})
    assert warm["ok"] and warm["cache_hit"]
    survivor_cache = caches[1 - owner_idx]
    assert survivor_cache.cross_process_hits >= 1

    # identical report through the cold and warm paths
    assert warm["result"]["issues"] == cold["result"]["issues"]
    assert warm["result"]["swc_ids"] == cold["result"]["swc_ids"]

    # the warm job's watcher still sees the full issue stream
    replay = list(gw.handle_stream({"op": "watch", "job_id": dup["job_id"]}))
    assert replay[0]["event"] == "issue"
    assert replay[0].get("source") == "cache"


def test_solver_memo_travels_through_shared_store(fleet):
    gw, services, caches = fleet
    resp = gw.handle({"op": "submit", "code": CODE, "name": "Memo"})
    assert gw.handle(
        {"op": "result", "job_id": resp["job_id"], "timeout": 10}
    )["ok"]
    owner_idx = int(resp["worker"][1:])
    other_cache = caches[1 - owner_idx]
    from mythril_tpu.fleet.hashring import code_key

    # the memo lands AFTER job.finish (same ordering as the real
    # finalizer), so a fast reader must allow the worker thread a beat
    deadline = time.monotonic() + 5.0
    memo = None
    while memo is None and time.monotonic() < deadline:
        memo = other_cache.get_solver_memo(code_key("", CODE))
        if memo is None:
            time.sleep(0.01)
    assert memo == {b"stub-digest": 1}


def test_fleet_stats_aggregate_two_workers(fleet):
    gw, _, _ = fleet
    stats = gw.handle({"op": "fleet_stats"})
    assert stats["ok"]
    assert set(stats["workers"]) == {"w0", "w1"}
    assert all(s is not None for s in stats["workers"].values())
    assert stats["gateway"]["workers_alive"] == 2


def test_device_workers_get_a_chip_each_and_no_more(monkeypatch, capsys):
    """One host, one chip per worker process: a process that starts JAX
    on an accelerator takes every chip it sees, so workers are confined
    to a chip each and the gateway refuses more workers than chips."""
    from mythril_tpu.fleet.worker import chip_env, device_slots
    from mythril_tpu.support import accelerators
    from mythril_tpu.interfaces import cli

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device_slots(3) == [None, None, None]

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(accelerators, "host_chip_count", lambda: 1)
    assert device_slots(1) == [0]
    assert chip_env(0)["TPU_VISIBLE_CHIPS"] == "0"
    with pytest.raises(ValueError, match="1 accelerator chip"):
        device_slots(2)
    with pytest.raises(SystemExit) as exit_info:
        cli.main(["gateway", "--spawn", "2", "--listen", "127.0.0.1:0"])
    assert exit_info.value.code == 1
    assert "2 device workers requested" in capsys.readouterr().err
