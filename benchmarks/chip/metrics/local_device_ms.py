"""Milliseconds per product of local compute on the device: the operations
under the program's ``repro.local`` scope (the fine step's gathers,
multiply, partial scatter-add and fold of the partials a chip owns; the
monoC step's block products), their union on each chip, mean over the
chips."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.device_ms(ctx, scopes.LOCAL, Path(__file__).resolve().parents[1])
