"""Host spans at the service's layer boundaries, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler runs (``jax.profiler.trace``) it lands in the trace beside the
device's operations, with ``args`` as integer (or string) stats on the
event; with no profiler running it costs about a microsecond. Nesting
gives each span its parent. Arguments known only when the work is done
go on through :func:`annotate`. Every argument is a host value the caller
already has: a span never waits for the device.

The stage-1 spans (``stage1.*``) and what each argument says are listed
in ``docs/scaling.md`` ("Tracing stage 1").
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools

from jax.profiler import TraceAnnotation

_batches = itertools.count()
_batch = contextvars.ContextVar("stage1_batch", default=-1)


def span(name: str, **args: int | str) -> TraceAnnotation:
    return TraceAnnotation(name, **args)


def annotate(s: TraceAnnotation, **args: int | str) -> None:
    """Add ``args`` to an open span; nothing when no profiler runs."""
    if TraceAnnotation.is_enabled():
        s.set_metadata(**args)


@contextlib.contextmanager
def batch(tasks: int):
    """The ``stage1.batch`` span of one ``select_pools_batch`` call. Its
    process-wide sequence number is the ``batch`` argument of every
    ``stage1.task`` span opened inside it (``current_batch``)."""
    n = next(_batches)
    token = _batch.set(n)
    try:
        with span("stage1.batch", batch=n, tasks=tasks):
            yield n
    finally:
        _batch.reset(token)


def current_batch() -> int:
    """Sequence number of the enclosing ``stage1.batch``, or -1."""
    return _batch.get()
