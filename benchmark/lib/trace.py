"""Capture a ``jax.profiler`` trace and reduce it to device metrics.

The reduction reads the XSpace file the profiler writes. A device is a
plane named ``/device:<KIND>:<n>``. Its op events (line ``XLA Ops``)
give the busy time, as the union of their intervals inside the traced
window, averaged over the devices. Executable events (line ``XLA
Modules``) give the device time of one jitted program by name. Idle
gaps are the holes in the busy union on the first device; each is
labelled by the host span that covers most of it: one of the harness's
own ``TraceAnnotation`` spans (named ``bench.*``) or one of the
program's round-loop phases (``obs.phase``), put on the trace's clock
through the harness's ``bench.traced`` span, which marks the window.
"""

import re
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

TRACED = "bench.traced"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z_]+:\d+$")
_DEVICE_LINES = ("XLA Ops", "XLA Modules")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def load_events(xspace_path: Optional[str] = None, serialized: Optional[bytes] = None) -> List[Event]:
    """The events the reduction reads, from an XSpace file or its bytes:
    device ops and executables, and the harness's host spans. Every
    other event is skipped."""
    from jax.profiler import ProfileData

    if serialized is not None:
        data = ProfileData.from_serialized_xspace(serialized)
    else:
        data = ProfileData.from_file(xspace_path)
    events = []
    add = events.append
    for plane in data.planes:
        pname = plane.name
        device = bool(_DEVICE_PLANE.match(pname))
        for line in plane.lines:
            lname = line.name
            if device and lname not in _DEVICE_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    start = ev.start_ns
                    add(Event(pname, lname, _op_name(name), start, start + ev.duration_ns))
                elif name.startswith("bench."):
                    start = ev.start_ns
                    add(Event(pname, lname, name, start, start + ev.duration_ns))
    return events


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _label(s: float, e: float, spans: List[Event]) -> str:
    """The shortest span that covers at least half of [s, e], else the
    span that covers most of it."""
    half = (e - s) / 2
    best, covered, shortest = "no span", 0.0, None
    for sp in spans:
        c = min(e, sp.end_ns) - max(s, sp.start_ns)
        length = sp.end_ns - sp.start_ns
        if c >= half and (shortest is None or length < shortest):
            best, shortest = sp.name, length
        elif shortest is None and c > covered:
            best, covered = sp.name, c
    return best


def reduce(events: List[Event], program: str = "jit__fused_impl", top: int = 10,
           host_spans: Optional[List[Event]] = None) -> Dict:
    """Busy, window and program seconds, the top device ops and the
    longest idle gaps. ``host_spans`` are more spans (the program's
    phases) on the trace's clock to label the gaps with."""
    host = [ev for ev in events if not _DEVICE_PLANE.match(ev.plane)]
    traced = [ev for ev in host if ev.name == TRACED]
    if traced:
        lo, hi = traced[0].start_ns, traced[0].end_ns
    else:
        lo = min(ev.start_ns for ev in events)
        hi = max(ev.end_ns for ev in events)
    by_device: Dict[str, List[Event]] = {}
    for ev in events:
        if _DEVICE_PLANE.match(ev.plane):
            by_device.setdefault(ev.plane, []).append(ev)
    busy, program_ns, op_ns = [], [], {}
    first_union: List[Tuple[float, float]] = []
    for plane in sorted(by_device):
        evs = by_device[plane]
        clipped = []
        prog = 0.0
        for ev in evs:
            c = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if c is None:
                continue
            if ev.line == "XLA Ops":
                clipped.append(c)
                op_ns[ev.name] = op_ns.get(ev.name, 0.0) + c[1] - c[0]
            elif ev.name.startswith(program):
                prog += c[1] - c[0]
        union = _union(clipped)
        if not first_union:
            first_union = union
        busy.append(sum(e - s for s, e in union))
        program_ns.append(prog)
    n = len(by_device)
    gaps = []
    if n:
        spans = [ev for ev in host if ev.name != TRACED] + list(host_spans or [])
        edges = [lo] + [x for iv in first_union for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append([_label(s, e, spans), (e - s) / 1e9])
        gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy) / n / 1e9) if n else 0.0,
        "program_s": (sum(program_ns) / n / 1e9) if n else 0.0,
        "device_ops": [[name, ns / n / 1e9] for name, ns in ops],
        "idle_gaps": gaps[:top],
    }


def program_spans(raw_events, t_traced: float, traced_start_ns: float) -> List[Event]:
    """The program's phase spans (``obs.TRACER.raw_events()``: kind,
    name, track, pid, perf_counter start, duration, args) on the trace's
    clock: ``t_traced`` is the perf_counter reading at which the
    ``bench.traced`` span began, ``traced_start_ns`` its trace time."""
    out = []
    for kind, name, track, _pid, ts, dur, _args in raw_events:
        if kind != "X" or track == "round":
            continue
        start = traced_start_ns + (ts - t_traced) * 1e9
        out.append(Event("program", track, name, start, start + dur * 1e9))
    return out


class Capture:
    """A profiler session of ``seconds`` from ``start()``, stopped by a
    timer thread (or by ``stop()``, whichever comes first). The trace is
    kept in memory (``xspace``, serialized XSpace bytes), not exported:
    on a TPU the export writes over 100 MB and takes about a minute."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._done = threading.Event()
        self.started = False
        self.t_traced: Optional[float] = None  # perf_counter at bench.traced's start
        self.stop_s: Optional[float] = None  # how long stopping the profiler took
        self.xspace: Optional[bytes] = None

    def start(self) -> None:
        import tempfile

        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        # the directory is required, and stays empty: the session is
        # stopped without an export
        jax.profiler.start_trace(tempfile.gettempdir(), profiler_options=options)
        self.started = True
        self._thread = threading.Thread(target=self._run, name="bench-trace", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        from jax._src import profiler

        try:
            self.t_traced = time.perf_counter()
            with jax.profiler.TraceAnnotation(TRACED):
                self._stop.wait(self.seconds)
        finally:
            t = time.perf_counter()
            with profiler._profile_state.lock:
                self.xspace = profiler._profile_state.profile_session.stop()
                profiler._profile_state.reset()
            self.stop_s = time.perf_counter() - t
            self._done.set()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._done.wait()
            self._thread.join()
