"""Milliseconds per product in the program's ``repro.unpack.reorder`` host
span: C's slot values put into canonical CSR order on the host."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.host_ms(ctx, "repro.unpack.reorder", Path(__file__).resolve().parents[1])
