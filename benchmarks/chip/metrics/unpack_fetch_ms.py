"""Milliseconds per product in the program's ``repro.unpack.fetch`` host
span: C's owned slots copied from the device to the host."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.host_ms(ctx, "repro.unpack.fetch", Path(__file__).resolve().parents[1])
