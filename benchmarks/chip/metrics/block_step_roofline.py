"""Share of the HBM roofline that the executor step reaches on block
operands, in percent.

The least time is the compulsory traffic of the product, every item of
every block of A, B and C moved once: (nnz(A) r k + nnz(B) k c + nnz(C) r c)
x itemsize bytes, from ``Cell.sizes()``'s block counts and its ``a_block``
(r, k) and ``b_block`` (k, c), over the chips' peak HBM bandwidth
(``peaks.json``, keyed by device kind).  The share is the least time over
the step's device time per product (``step_device_ms``)."""

from pathlib import Path

from benchmarks.chip.harness import load_module

step_device_ms = load_module(Path(__file__).with_name("step_device_ms.py"))


def least_seconds(sizes, chips, peaks):
    (r, k), (_, c) = sizes["a_block"], sizes["b_block"]
    items = sizes["nnz_a"] * r * k + sizes["nnz_b"] * k * c + sizes["nnz_c"] * r * c
    return items * sizes["itemsize"] / peaks["hbm_bytes_per_s"] / chips


def read(ctx):
    if ctx.window is None or ctx.peaks is None or not ctx.products:
        return None
    ns = step_device_ms.step_ns(ctx.window)
    if ns is None:
        return None
    step_s = ns / 1e9 / ctx.products
    return 100.0 * least_seconds(ctx.sizes, ctx.chips, ctx.peaks) / step_s
