"""Share of the traced window in which no operation ran on the device
(the online cell)."""


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - ctx.busy_s() / w) if w > 0 else None
