"""The program's own stage-1 spans in a trace (``stage1.*``, written by
``repro.core.spans``): which of them fall in the window, how they nest,
each span's self time, and the device's idle time inside it.

A span's children are the ``stage1.*`` spans of its thread nested
directly in it; its self intervals are the parts of its interval that
no child covers. Counters ride on the spans as their stats
(``Event.stats``).
"""
from __future__ import annotations

import bisect
import collections

from bench import trace

PREFIX = "stage1."


def stage1(events: list[trace.Event]) -> list[trace.Event]:
    """The ``stage1.*`` host spans that start inside ``bench.window``."""
    lo, hi = trace.window(events)
    return [e for e in trace.spans(events, PREFIX) if lo <= e.start_ns < hi]


def named(spans: list[trace.Event], name: str) -> list[trace.Event]:
    return [e for e in spans if e.name == name]


def self_parts(spans: list[trace.Event]
               ) -> list[tuple[trace.Event, list[tuple[float, float]]]]:
    """Each span with its self intervals, sorted by start."""
    by_thread = collections.defaultdict(list)
    for e in spans:
        by_thread[(e.plane, e.line)].append(e)
    kids: dict[int, list[tuple[float, float]]] = collections.defaultdict(list)
    ordered = []
    for evs in by_thread.values():
        evs.sort(key=lambda e: (e.start_ns, -e.end_ns))
        stack: list[trace.Event] = []
        for e in evs:
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            if stack:
                kids[id(stack[-1])].append((e.start_ns, e.end_ns))
            stack.append(e)
            ordered.append(e)
    out = []
    for e in sorted(ordered, key=lambda e: (e.start_ns, -e.end_ns)):
        covered = trace.union(trace.clip(kids[id(e)], e.start_ns, e.end_ns))
        parts, t = [], e.start_ns
        for s, end in covered:
            if s > t:
                parts.append((t, s))
            t = max(t, end)
        if e.end_ns > t:
            parts.append((t, e.end_ns))
        out.append((e, parts))
    return out


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def intervals(parts, name: str) -> list[tuple[float, float]]:
    """The self intervals of every span named ``name``, sorted."""
    return trace.union(iv for e, p in parts if e.name == name for iv in p)


def self_ns(parts, name: str) -> float:
    return sum(e - s for s, e in intervals(parts, name))


def idle_ns(events: list[trace.Event], parts, name: str) -> float | None:
    """Device-idle time inside the self intervals of the spans named
    ``name``; ``None`` where the trace holds no device plane."""
    if not trace.device_planes(events):
        return None
    return overlap_ns(trace.idle_gaps(events), intervals(parts, name))


def share_starting_inside(ops: list[trace.Event], ivs) -> float | None:
    """Share of the device time of ``ops`` whose operations start inside
    the sorted, disjoint intervals ``ivs``: where the host spans and the
    device share one clock, the operations a span waits for start in
    it."""
    total = sum(e.dur_ns for e in ops)
    if not total:
        return None
    starts = [s for s, _ in ivs]
    inside = 0.0
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < ivs[i][1]:
            inside += e.dur_ns
    return inside / total
