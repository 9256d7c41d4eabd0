"""Tokens trained per second: every step of the window over its whole
length, the last step waited for."""


def read(ctx):
    return ctx.items / ctx.window_s if ctx.item == "tokens" else None
