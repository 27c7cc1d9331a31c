"""Share of device busy time spent outside the serve-step program: the
admission work on the serving lane — prefill forwards of admitted rows,
their page scatters, prefix-publication copies and row surgery."""


def read(ctx):
    busy = ctx.busy_s()
    step = sum(e.dur for e in ctx.step_ops())
    if busy <= 0:
        return None
    return 100.0 * max(0.0, busy - step) / busy
