"""Roofline share of the fused aggregation + quality kernel
(``kernels/fedavg_agg.py``): the least time its bytes and FLOPs, counted
from each execution's ``(K, P)`` stack (``bench.counts``), could take on
the chip, over its device time in the traced window. Bytes bound it."""
from bench import counts, kernels


def read(ctx):
    if ctx.peaks is None:
        return None
    found = kernels.executions(ctx.events, kernels.FEDAVG_AGG_QUALITY)
    if not found:
        return None
    least = total = 0.0
    for seconds, shape in found:
        k, p = shape
        flops, nbytes = counts.fedavg_agg_quality_cost(k, p)
        least += counts.roofline_s(flops, nbytes, ctx.peaks)[0]
        total += seconds
    return 100.0 * least / total
