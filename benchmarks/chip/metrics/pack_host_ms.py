"""Milliseconds per product in the program's ``repro.pack`` host span:
``CompiledSpGEMM.pack``, the values into the executor's layout."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.host_ms(ctx, "repro.pack", Path(__file__).resolve().parents[1])
