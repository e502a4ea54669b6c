"""Milliseconds per product in the program's ``repro.call`` host span: the
executor's call, which coerces the values, copies them to the device and
dispatches the step without waiting for it."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.host_ms(ctx, "repro.call", Path(__file__).resolve().parents[1])
