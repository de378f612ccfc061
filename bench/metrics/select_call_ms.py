"""Host time of stage 1 per task: the benchmark's ``bench.select`` spans
around each ``select_pools_batch`` call in the traced window, summed,
over the tasks those calls served."""
from bench import trace


def read(ctx):
    lo, hi = trace.window(ctx.events)
    spans = [e for e in trace.spans(ctx.events, "bench.select")
             if lo <= e.start_ns < hi]
    tasks = ctx.counters.get("tasks", 0)
    if not spans or not tasks:
        return None
    return 1e-6 * sum(e.dur_ns for e in spans) / tasks
