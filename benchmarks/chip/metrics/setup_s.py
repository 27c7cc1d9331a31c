"""Seconds from the start of the process to the opening of the window:
imports, weights, proxies, engine, warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
