"""Host time of stage 1's level-1 passes per task: the self time of the
program's ``stage1.frontier`` spans in the traced window (the masked
ratios, the ``segmented_topk`` call and the fetch of its frontier), over
the ``stage1.task`` spans there."""
from bench import spans


def read(ctx):
    s = spans.stage1(ctx.events)
    tasks = spans.named(s, "stage1.task")
    if not tasks:
        return None
    return 1e-6 * spans.self_ns(spans.self_parts(s),
                                "stage1.frontier") / len(tasks)
