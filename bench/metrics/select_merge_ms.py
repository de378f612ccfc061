"""Host time of stage 1's exact merge per task: the self time of the
program's ``stage1.merge`` spans in the traced window (rescoring the
candidates, their sort, the budget scan and the escalation test), over
the ``stage1.task`` spans there."""
from bench import spans


def read(ctx):
    s = spans.stage1(ctx.events)
    tasks = spans.named(s, "stage1.task")
    if not tasks:
        return None
    return 1e-6 * spans.self_ns(spans.self_parts(s),
                                "stage1.merge") / len(tasks)
