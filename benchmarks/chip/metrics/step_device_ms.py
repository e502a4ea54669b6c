"""Milliseconds per product of the executor step on the device: the time of
the programs (XLA modules) that ran on each chip in the traced window,
averaged over the chips.  The window runs no other program: packing and
unpacking are host code."""

from benchmarks.chip import trace


def step_ns(window):
    """Per-chip mean of program time in the window, or None without any."""
    per_dev = {dev: sum(e - s for _, s, e in mods) for dev, mods in window.modules.items()}
    if not per_dev or not any(per_dev.values()):
        return None
    return trace.mean(per_dev)


def read(ctx):
    if ctx.window is None or not ctx.products:
        return None
    ns = step_ns(ctx.window)
    return None if ns is None else ns / 1e6 / ctx.products
