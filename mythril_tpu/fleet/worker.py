"""Worker handles: how the gateway talks to one AnalysisService.

Two implementations of the same tiny contract (``name``, ``request``,
``stream``):

  * :class:`SocketWorker` — the production shape: a worker PROCESS
    started as ``myth serve --socket PATH --store DIR`` (each owning a
    device or mesh slice), reached over the bounded line-JSON
    transport. :func:`spawn_worker` launches one and
    :func:`wait_for_socket` gates on its socket appearing.
  * :class:`LocalWorker` — an in-process AnalysisService behind the
    same interface, for tests and the check.sh fleet smoke. NOTE the
    multi-tenant invariant I2 (docs/SERVICE.md): two REAL pipelines in
    one process would share process-global singletons under different
    host locks, so in-process fleets must stub the pipeline
    (tests/service/test_scheduler.py's StubbedService idiom) — real
    fleets always use subprocess workers.

The gateway holds handles, not sockets: worker-death detection and
re-route live in gateway.py and only need ConnectionError/OSError out
of these calls.
"""

import os
import subprocess
import sys
import time
from typing import Dict, Iterator, List, Optional

from mythril_tpu.fleet import transport


class SocketWorker:
    """A worker process reached over its service socket."""

    def __init__(self, name: str, address: str):
        self.name = name
        self.address = address

    def request(self, payload: Dict, timeout: Optional[float] = None) -> Dict:
        return transport.request(self.address, payload, timeout=timeout)

    def stream(
        self, payload: Dict, timeout: Optional[float] = None
    ) -> Iterator[Dict]:
        return transport.stream(self.address, payload, timeout=timeout)


class LocalWorker:
    """An in-process AnalysisService behind the worker contract."""

    def __init__(self, name: str, service):
        self.name = name
        self.service = service

    def request(self, payload: Dict, timeout: Optional[float] = None) -> Dict:
        from mythril_tpu.service.api import handle_request

        return handle_request(self.service, payload)

    def stream(
        self, payload: Dict, timeout: Optional[float] = None
    ) -> Iterator[Dict]:
        from mythril_tpu.service.api import stream_watch

        return stream_watch(self.service, payload)


def _myth_argv() -> List[str]:
    """argv prefix that reaches the `myth` CLI from this checkout."""
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return [sys.executable, os.path.join(root, "myth")]


def device_slots(n_workers: int) -> List[Optional[int]]:
    """The chip each of ``n_workers`` worker processes on this host gets.

    A process that initializes JAX on an accelerator takes every chip it
    can see, so a second one would get none. Each worker is therefore
    given its own chip, and asking for more workers than the host has
    chips is refused. Workers held to the CPU (JAX_PLATFORMS=cpu) need
    no chip: their slots are None."""
    from mythril_tpu.support.accelerators import host_chip_count, runs_on_cpu

    if runs_on_cpu():
        return [None] * n_workers
    chips = host_chip_count()
    if n_workers > chips:
        raise ValueError(
            "%d device workers requested but this host has %d accelerator "
            "chip(s); each worker needs a chip of its own (run workers "
            "with JAX_PLATFORMS=cpu to use the CPU)" % (n_workers, chips)
        )
    return list(range(n_workers))


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that confines a process to one TPU chip of the host,
    with a runtime port of its own so processes on one host do not
    collide."""
    port = str(8476 + chip)
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": port,
        "TPU_PROCESS_ADDRESSES": "localhost:" + port,
    }


def spawn_worker(
    socket_path: str,
    store_dir: Optional[str] = None,
    workers: int = 1,
    queue_size: int = 16,
    warm: bool = False,
    lanes: Optional[int] = None,
    env: Optional[Dict[str, str]] = None,
    stderr=None,
    chip: Optional[int] = None,
) -> subprocess.Popen:
    """Launch one fleet worker process (``myth serve --socket ...``),
    confined to ``chip`` when one is given (see device_slots)."""
    argv = _myth_argv() + [
        "serve",
        "--socket", socket_path,
        "--workers", str(workers),
        "--queue-size", str(queue_size),
    ]
    if store_dir:
        argv += ["--store", store_dir]
    if not warm:
        argv += ["--no-warm"]
    if lanes:
        argv += ["--lanes", str(lanes)]
    child_env = dict(os.environ)
    if chip is not None:
        child_env.update(chip_env(chip))
    if env:
        child_env.update(env)
    return subprocess.Popen(argv, env=child_env, stderr=stderr)


def wait_for_socket(
    socket_path: str,
    timeout_s: float = 60.0,
    process: Optional[subprocess.Popen] = None,
) -> None:
    """Block until the worker's socket answers a ping (or die with the
    worker: a child that exited during startup fails fast, not at the
    deadline)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if process is not None and process.poll() is not None:
            raise RuntimeError(
                "worker exited rc=%s before serving %s"
                % (process.returncode, socket_path)
            )
        if os.path.exists(socket_path):
            try:
                response = transport.request(
                    socket_path, {"op": "ping"}, timeout=2.0
                )
                if response.get("pong"):
                    return
            except (OSError, ValueError):
                pass
        time.sleep(0.2)
    raise TimeoutError(
        "worker socket %s not serving after %.0fs" % (socket_path, timeout_s)
    )
