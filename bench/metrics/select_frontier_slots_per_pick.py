"""Frontier slots the ``segmented_topk`` kernel extracts per client
picked: the sum of ``shards`` x ``F`` over the program's
``stage1.frontier`` spans, over the sum of ``picks`` over its
``stage1.task`` spans, in the traced window. Every slot past one a pick
is extraction the answer does not use."""
from bench import spans


def read(ctx):
    s = spans.stage1(ctx.events)
    picks = sum(e.stats.get("picks", 0)
                for e in spans.named(s, "stage1.task"))
    if not picks:
        return None
    slots = sum(e.stats["shards"] * e.stats["F"]
                for e in spans.named(s, "stage1.frontier"))
    return slots / picks
