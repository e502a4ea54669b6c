"""Seconds per product: the window's seconds over the products completed
in it, one caller in a closed loop (host clock)."""


def read(ctx):
    return ctx.window_s / ctx.products if ctx.products else None
