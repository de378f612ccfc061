"""Share of the traced window in which no operation ran on the device,
in the training cells: one minus the busy union over the window."""
from bench import trace


def read(ctx):
    if not trace.device_planes(ctx.events) or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
