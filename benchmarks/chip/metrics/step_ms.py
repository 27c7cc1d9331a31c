"""Device time of the serve-step program per step in the traced
window, in milliseconds."""


def read(ctx):
    mods = ctx.step_modules()
    if not mods:
        return None
    return 1e3 * sum(e.dur for e in mods) / len(mods)
