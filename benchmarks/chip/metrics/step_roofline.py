"""Share of the HBM roofline that the executor step reaches, in percent.

The least time is the compulsory traffic of the product, every value of A,
B and C moved once, (nnz(A) + nnz(B) + nnz(C)) x itemsize bytes, over the
chips' peak HBM bandwidth (``peaks.json``, keyed by device kind).  The
share is the least time over the step's device time per product
(``step_device_ms``)."""

from pathlib import Path

from benchmarks.chip.harness import load_module

step_device_ms = load_module(Path(__file__).with_name("step_device_ms.py"))


def least_seconds(sizes, chips, peaks):
    bytes_moved = (sizes["nnz_a"] + sizes["nnz_b"] + sizes["nnz_c"]) * sizes["itemsize"]
    return bytes_moved / peaks["hbm_bytes_per_s"] / chips


def read(ctx):
    if ctx.window is None or ctx.peaks is None or not ctx.products:
        return None
    ns = step_device_ms.step_ns(ctx.window)
    if ns is None:
        return None
    step_s = ns / 1e9 / ctx.products
    return 100.0 * least_seconds(ctx.sizes, ctx.chips, ctx.peaks) / step_s
