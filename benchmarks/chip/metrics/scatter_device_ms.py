"""Milliseconds per product of the value scatter on the device: the
operations under the program's ``repro.scatter_values`` scope (the value
scatters into the owned tables), their union on each chip, mean over the
chips."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.device_ms(ctx, scopes.SCATTER, Path(__file__).resolve().parents[1])
