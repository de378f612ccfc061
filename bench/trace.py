"""From a profiler trace to the numbers the per-layer metrics read.

``load`` flattens an ``.xplane.pb`` into :class:`Event` s; everything
else works on that list, so the reduction can be checked on a small
recorded trace (``bench/tests``) without a chip. Device planes are those
named ``/device:TPU:<n>``; the benchmark's own host spans are the events
whose name starts with ``bench.`` (``jax.profiler.TraceAnnotation``), and
``bench.window`` bounds the measured window.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Iterable

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _plain(v):
    return v if isinstance(v, (int, float, str, bool)) or v is None \
        else str(v)


def load(path: str) -> list[Event]:
    """Every event of every plane and line of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 {k: _plain(v) for k, v in e.stats}))
    return out


def dump(events: Iterable[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.asdict(e) for e in events], f)


def read_dump(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(**e) for e in json.load(f)]


# ---------------------------------------------------------------------------
# selections
# ---------------------------------------------------------------------------

def window(events: list[Event]) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the ``bench.window`` span."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no bench.window span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def device_ops(events: list[Event], plane: str | None = None,
               line: str = OPS_LINE) -> list[Event]:
    return [e for e in events if e.plane.startswith(DEVICE_PREFIX)
            and e.line == line and (plane is None or e.plane == plane)]


def spans(events: list[Event], prefix: str = SPAN_PREFIX) -> list[Event]:
    return [e for e in events if e.name.startswith(prefix)
            and not e.plane.startswith(DEVICE_PREFIX)]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def busy_s(events: list[Event]) -> float:
    """Seconds in which an operation ran on the device inside the window,
    averaged over the device planes that ran any."""
    lo, hi = window(events)
    planes = device_planes(events)
    per = []
    for p in planes:
        iv = union(clip(((e.start_ns, e.end_ns)
                         for e in device_ops(events, p)), lo, hi))
        if iv:
            per.append(sum(e - s for s, e in iv) * 1e-9)
    return sum(per) / len(per) if per else 0.0


def window_s(events: list[Event]) -> float:
    lo, hi = window(events)
    return (hi - lo) * 1e-9


def op_seconds(events: list[Event], match) -> tuple[float, int]:
    """Summed device seconds and count of the window's device operations
    whose event satisfies ``match``."""
    lo, hi = window(events)
    total, n = 0.0, 0
    for e in device_ops(events):
        if lo <= e.start_ns < hi and match(e):
            total += e.dur_ns * 1e-9
            n += 1
    return total, n


def module_seconds(events: list[Event], prefix: str) -> tuple[float, int]:
    """Summed device seconds and count of the window's executions of the
    compiled programs whose name starts with ``prefix``."""
    lo, hi = window(events)
    total, n = 0.0, 0
    for e in device_ops(events, line=MODULES_LINE):
        if lo <= e.start_ns < hi and e.name.startswith(prefix):
            total += e.dur_ns * 1e-9
            n += 1
    return total, n


def idle_gaps(events: list[Event]) -> list[tuple[float, float]]:
    """Idle intervals of the first device plane inside the window."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return [(lo, hi)]
    busy = union(clip(((e.start_ns, e.end_ns)
                       for e in device_ops(events, planes[0])), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(host_spans: list[Event], t: float) -> str:
    """Name of the innermost benchmark span (other than the window)
    open at ``t``, or ``"outside spans"``."""
    best = None
    for e in host_spans:
        if e.name != WINDOW_SPAN and e.start_ns <= t < e.end_ns:
            if best is None or e.dur_ns < best.dur_ns:
                best = e
    return best.name if best is not None else "outside spans"


def op_label(e: Event) -> str:
    """A device operation's name for the breakdown: the HLO instruction
    name, where the event's name is the instruction's text."""
    name = e.name.split(" = ", 1)[0]
    return name.lstrip("%")


def breakdown(events: list[Event], top: int = 10) -> dict:
    """The device operations that took most time in the window, and the
    longest idle gaps, each named by the benchmark span the host was in."""
    lo, hi = window(events)
    planes = device_planes(events)
    per_op: dict[str, float] = {}
    for e in device_ops(events, planes[0] if planes else None):
        if lo <= e.start_ns < hi:
            k = op_label(e)
            per_op[k] = per_op.get(k, 0.0) + e.dur_ns * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    host = spans(events)
    gaps = sorted(idle_gaps(events), key=lambda g: g[0] - g[1])[:top]
    idle = [[span_at(host, (s + e) / 2), (e - s) * 1e-9] for s, e in gaps]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
