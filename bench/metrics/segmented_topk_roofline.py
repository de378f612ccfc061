"""Roofline share of the segmented top-k kernel
(``kernels/segmented_topk.py``): the least time a per-shard top-F needs
over the fleet's shards (every key read once, F values and indices per
shard written once, ``bench.counts``), over the device time of the
kernel's passes in the traced window. Bytes bound it; iterative
extraction is bounded by neither."""
from bench import counts, kernels


def read(ctx):
    if ctx.peaks is None:
        return None
    c = ctx.counters
    if "shards" not in c:
        return None
    found = kernels.topk_calls(ctx.events, c["shards"], c["shard_width"])
    if not found:
        return None
    least = total = 0.0
    for seconds, (segments, width, k) in found:
        ops, nbytes = counts.segmented_topk_cost(segments, width, k)
        least += counts.roofline_s(ops, nbytes, ctx.peaks)[0]
        total += seconds
    return 100.0 * least / total
