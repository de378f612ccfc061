"""Model FLOP utilization of training: the forward and backward FLOPs of
the clients' local steps (counted from the CNN's shapes,
``bench.counts``), times the client updates per second of the traced
window, over the peak of all the chips the cell uses. Padded slots and
dropped clients, which the devices also compute, do not count."""


def read(ctx):
    c = ctx.counters
    if not c.get("updates") or ctx.peaks is None:
        return None
    flops_per_s = c["train_flops_per_update"] * c["updates"] / c["seconds"]
    return 100.0 * flops_per_s / (ctx.chips * ctx.peaks["flops_per_s"])
