"""Milliseconds per product of the routed exchange on the device: the
operations under the program's ``repro.expand_a``, ``repro.expand_b`` and
``repro.reduce_c`` scopes (send-buffer gathers, ``all_to_all``, the tables
built from what arrives, the fold of arrivals), their union on each chip,
mean over the chips."""

from pathlib import Path

from benchmarks.chip import scopes


def read(ctx):
    return scopes.device_ms(ctx, scopes.EXCHANGE, Path(__file__).resolve().parents[1])
