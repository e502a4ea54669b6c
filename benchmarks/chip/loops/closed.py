"""A closed loop: one caller, each product starting when the last one
ends, no batching.

Parameters from the traffic mix: ``pool``, the value sets built from the
seed in set-up, taken in turn; ``warmup``, the products run in set-up;
``check_sample``, how many of the window's outputs the check compares.
"""
import sys
import time

from benchmarks.chip.harness import Reservoir, WindowResult


def warm(cell) -> None:
    for i in range(cell.traffic["warmup"]):
        cell.product(*cell.pool[i % len(cell.pool)])


def drive(cell, seconds: float, seed: int) -> WindowResult:
    """Products until ``seconds`` have passed; the window closes with the
    product that ends past them."""
    keep = Reservoir(cell.traffic["check_sample"], seed)
    latencies, failed = [], 0
    pool = cell.pool
    start = time.perf_counter()
    deadline = start + seconds
    i = 0
    while True:
        t = time.perf_counter()
        try:
            out = cell.product(*pool[i % len(pool)])
        except Exception as exc:  # a product that fails counts, and fails the run
            print(f"product {i} failed: {exc!r}", file=sys.stderr)
            out, failed = None, failed + 1
        end = time.perf_counter()
        latencies.append(end - t)
        if out is not None:
            keep.offer(i % len(pool), out)
        i += 1
        if end >= deadline:
            break
    return WindowResult(end - start, latencies, failed, keep.sample)
