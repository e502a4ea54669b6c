"""Milliseconds per product of the routed exchange: the device time of the
trace's all-to-all operations, averaged over the chips."""

from benchmarks.chip import trace

PATTERN = r"all-to-all|all_to_all|alltoall"


def read(ctx):
    if ctx.window is None or not ctx.products:
        return None
    per_dev = {
        dev: sum(trace.by_name(ops, PATTERN).values()) for dev, ops in ctx.window.ops.items()
    }
    ns = trace.mean(per_dev)
    return ns / 1e6 / ctx.products if ns else None
