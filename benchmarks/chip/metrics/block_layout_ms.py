"""Milliseconds per product of the block-layout change on the device: the
operations under the program's ``repro.block_layout`` scope (A's and B's
values from the caller's block-after-block order to the items-major
tables the step reads, and C's back), their union on each chip, mean over
the chips.  None where the program has no such scope, as a scalar cell's
has not."""

from pathlib import Path

from benchmarks.chip import scopes

BLOCK_LAYOUT = ("repro.block_layout",)


def read(ctx):
    return scopes.device_ms(ctx, BLOCK_LAYOUT, Path(__file__).resolve().parents[1])
