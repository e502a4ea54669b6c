"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips the cell uses."""

from benchmarks.chip import trace


def read(ctx):
    if ctx.window is None or ctx.window.hi <= ctx.window.lo:
        return None
    span = ctx.window.hi - ctx.window.lo
    busy = trace.mean(ctx.window.busy_ns)
    return 100.0 * (1.0 - busy / span)
