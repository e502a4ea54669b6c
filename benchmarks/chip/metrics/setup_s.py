"""Seconds from process start to the first timed product: structures,
plan or restore, compile, value pool, warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
