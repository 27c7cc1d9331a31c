"""Tokens committed inside the window over the window's seconds
(every committed token counts, whether or not its request finished)."""


def read(ctx):
    return ctx.win.tokens / ctx.win.seconds
