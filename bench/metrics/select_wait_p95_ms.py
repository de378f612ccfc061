"""The tail of the tasks' waits in a stage-1 cell offered above what
stage 1 sustains: from each task's due time to the return of the call
that served it, p95 over every task due in the window. The queue grows
all through such a run, so the tail swings with the smallest change."""


def read(ctx):
    return ctx.counters.get("wait_p95_ms")
