"""Where the program's Pallas kernels show up in a device trace, and the
shapes they ran at, read from the events themselves.

On a TPU each ``XLA Ops`` event is named by its HLO instruction's text,
``%<name>.<n> = (<outputs>) custom-call(<operands>), ...``; a Pallas
kernel's instruction takes the name of the jitted function that wraps
its ``pallas_call``. Programs are ``XLA Modules`` events named
``jit_<function>(<fingerprint>)``.
"""
from __future__ import annotations

import re

from bench import trace

FEDAVG_AGG_QUALITY = "fedavg_agg_quality"     # kernels/fedavg_agg.py
SEGMENTED_TOPK = "segmented_topk"             # kernels/segmented_topk.py

_SHAPE = re.compile(r"f32\[(\d+(?:,\d+)*)\]")


def instruction(e: trace.Event) -> str:
    """``fusion.3`` from ``%fusion.3 = ...``: the HLO instruction name."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def is_kernel(e: trace.Event, kernel: str) -> bool:
    """Whether ``e`` is a custom call of the kernel named ``kernel``."""
    return (" custom-call(" in e.name
            and re.fullmatch(rf"{re.escape(kernel)}(\.\d+)?",
                             instruction(e)) is not None)


def shapes(e: trace.Event) -> list[tuple[int, ...]]:
    """Every f32 array shape in the instruction's text: its outputs
    first, then its operands."""
    return [tuple(int(x) for x in m.split(","))
            for m in _SHAPE.findall(e.name)]


def _in_window(events, kernel):
    lo, hi = trace.window(events)
    return [e for e in trace.device_ops(events)
            if lo <= e.start_ns < hi and is_kernel(e, kernel)]


def executions(events, kernel: str):
    """``[(device seconds, (K, P))]`` of each execution of the fused
    aggregation kernel in the window: ``(K, P)`` is its widest 2-D f32
    array, the stacked client updates it reads."""
    out = []
    for e in _in_window(events, kernel):
        two = [s for s in shapes(e) if len(s) == 2]
        if two:
            out.append((e.dur_ns * 1e-9, max(two, key=lambda s: s[1])))
    return out


def topk_calls(events, segments: int, width: int):
    """``[(device seconds, (segments, width, k))]`` of each
    ``segmented_topk`` call in the window: the summed time of its kernel
    passes inside one execution of its program, and the frontier ``k``
    its passes write (``(tiles, rows, k)`` outputs)."""
    lo, hi = trace.window(events)
    mods = [m for m in trace.device_ops(events, line=trace.MODULES_LINE)
            if lo <= m.start_ns < hi
            and m.name.startswith(f"jit_{SEGMENTED_TOPK}")]
    passes = _in_window(events, SEGMENTED_TOPK)
    out = []
    for m in mods:
        inside = [p for p in passes if m.start_ns <= p.start_ns < m.end_ns]
        ks = {s[2] for p in inside for s in shapes(p)[:1] if len(s) == 3}
        if inside and len(ks) == 1:
            out.append((sum(p.dur_ns for p in inside) * 1e-9,
                        (segments, width, ks.pop())))
    return out
