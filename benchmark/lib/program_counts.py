"""Shared arithmetic of the readers that read the program's own span
tracer (``mythril_tpu.obs.TRACER``): its spans and its counter events.

The harness turns the tracer on when the traced window opens and never
off, so its events are the window's: a counter event carries one
increment of a catalog counter (``obs.count``), and their sum over the
events is the window's count, with no snapshot taken at the window's
edges. A program that records no such span or counter (one older than
them) leaves the reader with nothing to read; so does a tracer that
dropped events, since a sum over what it kept would be short. The
readers then return None and the line leaves the metric out.
"""

import re
from typing import Dict, List, Optional, Sequence

_SERIES = re.compile(r'^(?P<name>[a-z_]+)\{(?P<labels>.*)\}$')
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def window_events(tracer=None) -> Optional[List[tuple]]:
    """The tracer's raw events (kind, name, track, pid, start, duration,
    args), or None where it recorded nothing or dropped any."""
    if tracer is None:
        from mythril_tpu import obs

        tracer = obs.TRACER
    if tracer.dropped > 0:
        return None
    events = tracer.raw_events()
    return events or None


def span_seconds(events: Sequence[tuple], name: str) -> float:
    """Summed duration of the spans named ``name``. Spans of one name do
    not nest in each other, so this is that phase's time."""
    return sum(e[5] for e in events if e[0] == "X" and e[1] == name)


def counter_total(events: Sequence[tuple], name: str, **labels: str) -> float:
    """Summed increments of counter ``name`` whose labels include
    ``labels``."""
    total = 0.0
    for kind, ev_name, _track, _pid, _ts, _dur, args in events:
        if kind != "C" or ev_name != name:
            continue
        have = args.get("labels") or {}
        if all(have.get(k) == v for k, v in labels.items()):
            total += args["amount"]
    return total


def counter_by_label(events: Sequence[tuple], name: str, label: str) -> Dict[str, float]:
    """Summed increments of counter ``name`` per value of ``label``."""
    out: Dict[str, float] = {}
    for kind, ev_name, _track, _pid, _ts, _dur, args in events:
        if kind == "C" and ev_name == name:
            value = (args.get("labels") or {}).get(label)
            out[value] = out.get(value, 0.0) + args["amount"]
    return out


def span_share(result, name: str, tracer=None) -> Optional[float]:
    """A phase's window seconds over the window's wall."""
    events = window_events(tracer)
    if events is None or not result.window_s:
        return None
    seconds = span_seconds(events, name)
    if seconds <= 0:
        return None
    return seconds / result.window_s


def registry_series(snapshot: Dict[str, float], name: str, label: str) -> Dict[str, float]:
    """The series of counter ``name`` in a registry snapshot
    (``obs.REGISTRY.snapshot()``), keyed by the value of ``label``."""
    out = {}
    for key, value in snapshot.items():
        m = _SERIES.match(key)
        if m and m.group("name") == name:
            labels = dict(_LABEL.findall(m.group("labels")))
            if label in labels:
                out[labels[label]] = float(value)
    return out


def annotation_offsets(xspace: bytes, events: Sequence[tuple], t_traced: float) -> Dict:
    """How far the profiler's copies of the program's spans (the
    ``TraceAnnotation`` each span opens while tracing) lie from the
    spans' own ``perf_counter`` starts put on the trace's clock through
    the ``bench.traced`` anchor (benchmark/lib/trace.py program_spans).
    Each annotation is paired with the nearest start of a span of its
    name; returns the count paired, the largest absolute offset and the
    median offset (annotation minus mapped start) in microseconds, or
    an empty dict where nothing pairs."""
    import bisect

    from jax.profiler import ProfileData

    from benchmark.lib.trace import TRACED

    names = {e[1] for e in events if e[0] == "X"}
    data = ProfileData.from_serialized_xspace(xspace)
    anchor, annotations = None, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == TRACED and anchor is None:
                    anchor = ev.start_ns
                elif ev.name in names:
                    annotations.append((ev.name, ev.start_ns))
    if anchor is None or not annotations:
        return {}
    starts: Dict[str, List[float]] = {}
    for kind, name, _track, _pid, ts, _dur, _args in events:
        if kind == "X":
            starts.setdefault(name, []).append(anchor + (ts - t_traced) * 1e9)
    for mapped in starts.values():
        mapped.sort()
    offsets = []
    for name, start_ns in annotations:
        mapped = starts.get(name)
        if not mapped:
            continue
        i = bisect.bisect_left(mapped, start_ns)
        near = [mapped[j] for j in (i - 1, i) if 0 <= j < len(mapped)]
        offsets.append(min((start_ns - m for m in near), key=abs) / 1e3)
    if not offsets:
        return {}
    offsets.sort()
    return {"paired": len(offsets), "max_abs_us": max(abs(o) for o in offsets),
            "median_us": offsets[len(offsets) // 2]}


def enclosing_split(events: Sequence[tuple], name: str) -> Dict[str, float]:
    """Seconds of the spans named ``name`` by the innermost other span
    around each (``"none"`` where no span encloses it): where a nested
    phase such as ``host_sat`` was spent. Round spans are skipped.
    Events carry no thread, so enclosure is by time alone: a solve on
    another thread (the service's async solver pool, which a lone
    analysis never starts) would be credited to the phase it overlaps."""
    import bisect

    by_name: Dict[str, List[tuple]] = {}
    for kind, ev_name, track, _pid, ts, dur, _args in events:
        if kind == "X" and ev_name != name and track != "round":
            by_name.setdefault(ev_name, []).append((ts, ts + dur))
    for spans in by_name.values():
        spans.sort()
    starts = {n: [s for s, _ in spans] for n, spans in by_name.items()}
    out: Dict[str, float] = {}
    for kind, ev_name, _track, _pid, ts, dur, _args in events:
        if kind != "X" or ev_name != name:
            continue
        best, best_len = "none", None
        for other, spans in by_name.items():
            i = bisect.bisect_right(starts[other], ts) - 1
            if i < 0:
                continue
            s, e = spans[i]
            if e >= ts + dur and (best_len is None or e - s < best_len):
                best, best_len = other, e - s
        out[best] = out.get(best, 0.0) + dur
    return out
