"""Level-1 passes stage 1 makes per task: the program's
``stage1.frontier`` spans over its ``stage1.task`` spans in the traced
window. One where the first frontier suffices; each escalation adds a
pass at twice the frontier."""
from bench import spans


def read(ctx):
    s = spans.stage1(ctx.events)
    tasks = spans.named(s, "stage1.task")
    if not tasks:
        return None
    return len(spans.named(s, "stage1.frontier")) / len(tasks)
