"""Share of the traced window in which no operation ran on a chip, in
the training cells: each chip's own idle share (one minus its busy
union over the window), averaged over the chips the cell uses. An idle
chip counts in full; a union over the chips would hide it."""


def read(ctx):
    if ctx.window_s <= 0 or not any(ctx.device_busy_s):
        return None
    return 100.0 * sum(1.0 - b / ctx.window_s
                       for b in ctx.device_busy_s) / len(ctx.device_busy_s)
