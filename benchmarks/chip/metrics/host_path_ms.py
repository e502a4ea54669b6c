"""Milliseconds per product on the caller's host path: packing the values
(``bench.pack``) and bringing C back in canonical order (``bench.unpack``),
from the benchmark's host spans in the traced window."""

HOST_SPANS = ("bench.pack", "bench.unpack")


def read(ctx):
    if ctx.window is None or not ctx.products:
        return None
    ns = sum(e - s for name, s, e in ctx.spans if name in HOST_SPANS)
    return ns / 1e6 / ctx.products if ns else None
