"""Idle share of the most idle chip in the training cells: one minus its
busy union over the traced window. Beside ``idle_share.train`` (the
mean over the chips) it shows tenants placed unevenly, or a chip whose
tenants wait on the host while another's queue."""


def read(ctx):
    if ctx.window_s <= 0 or not any(ctx.device_busy_s):
        return None
    return 100.0 * (1.0 - min(ctx.device_busy_s) / ctx.window_s)
