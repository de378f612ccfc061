"""Device idle time during stage 1's exact merge per task: the part of
the self intervals of the program's ``stage1.merge`` spans in which no
operation ran on the device, over the ``stage1.task`` spans in the
traced window."""
from bench import spans


def read(ctx):
    s = spans.stage1(ctx.events)
    tasks = spans.named(s, "stage1.task")
    if not tasks:
        return None
    idle = spans.idle_ns(ctx.events, spans.self_parts(s), "stage1.merge")
    return None if idle is None else 1e-6 * idle / len(tasks)
