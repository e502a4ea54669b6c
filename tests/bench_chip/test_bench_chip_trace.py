"""The trace reduction's arithmetic, on a trace that the test records on the
CPU: union of intervals, idle gaps named by host span, and per-product
division."""
from __future__ import annotations

import time

import numpy as np
import pytest
from conftest import cpu_lines

from benchmarks.chip import harness, trace


def brute_union_ns(intervals, lo, hi) -> int:
    """Covered length of [lo, hi] by a sweep over sorted endpoints."""
    edges = sorted([(max(s, lo), 1) for _, s, e in intervals if min(e, hi) > max(s, lo)]
                   + [(min(e, hi), -1) for _, s, e in intervals if min(e, hi) > max(s, lo)])
    covered, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    return covered


def test_union_gaps_and_attribution_by_hand():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 35, 38), ("e", 60, 70)]
    merged = trace.union(ops)
    assert merged == [(0, 20), (30, 40), (60, 70)]
    assert trace.total(merged) == 40 == brute_union_ns(ops, 0, 100)
    assert trace.gaps(merged, 0, 100) == [(20, 30), (40, 60), (70, 100)]
    assert trace.gaps(merged, 8, 65) == [(20, 30), (40, 60)]
    spans = [("bench.window", 0, 100), ("bench.pack", 18, 32), ("bench.unpack", 45, 55)]
    assert trace.attribute(trace.gaps(merged, 0, 100), spans) == {
        "bench.pack": 10, "bench.unpack": 10, "bench.window": 40}
    assert trace.open_span(spans, 200) == trace.NO_SPAN
    assert trace.by_name(ops + [("all-to-all.3", 80, 90)], r"all-to-all") == {"all-to-all.3": 10}


def test_op_names_are_cut_from_hlo_text():
    assert trace.op_name("%fusion.2 = f32[8]{0:T(1024)S(1)} fusion(s32[8]{0} %p), "
                         "kind=kLoop") == "fusion.2 (fusion)"
    assert trace.op_name("%sort.1 = (s32[8]{0:T(1024)}, f32[8]{0}) sort(s32[8]{0} %b)") \
        == "sort.1 (sort)"
    assert trace.op_name("%all-to-all.3 = f32[4,9]{1,0} all-to-all(f32[4,9]{1,0} %x)") \
        == "all-to-all.3 (all-to-all)"
    assert trace.op_name("dot_general.1") == "dot_general.1"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A CPU trace of a window of products, each a packing span, a jitted
    program in a step span and a sleeping unpack span."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.cumsum(x @ x, axis=0).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    tdir = tmp_path_factory.mktemp("trace")
    n = 4
    jax.profiler.start_trace(str(tdir))
    with TraceAnnotation(harness.WINDOW_SPAN):
        for _ in range(n):
            with TraceAnnotation("bench.pack"):
                time.sleep(0.004)
            with TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with TraceAnnotation("bench.unpack"):
                time.sleep(0.006)
    jax.profiler.stop_trace()
    return trace.load(trace.xplane_file(tdir), cpu_lines), n


def test_recorded_union_and_gaps_cover_the_window(recorded):
    rec, _ = recorded
    win = trace.reduce(rec, harness.WINDOW_SPAN)
    assert win.ops, rec.lines
    for dev, ops in win.ops.items():
        busy = win.busy_ns[dev]
        assert busy == brute_union_ns(ops, win.lo, win.hi) > 0
        assert busy <= sum(e - s for _, s, e in ops)
        idle = sum(win.idle_by_span[dev].values())
        assert busy + idle == win.hi - win.lo


def test_recorded_gaps_are_named_by_the_host_span_open(recorded):
    rec, n = recorded
    win = trace.reduce(rec, harness.WINDOW_SPAN)
    idle = win.idle_by_span[next(iter(win.idle_by_span))]
    # the host sleeps 4 ms in each packing span and 6 ms in each unpacking one
    assert idle.get("bench.pack", 0) >= 0.8 * n * 4e6
    assert idle.get("bench.unpack", 0) >= 0.8 * n * 6e6
    assert idle.get("bench.unpack", 0) > idle.get("bench.pack", 0)


def test_recorded_per_product_division(recorded, tiny_bench):
    rec, n = recorded
    win = trace.reduce(rec, harness.WINDOW_SPAN)
    spans = trace.clip(rec.spans, win.lo, win.hi)
    ctx = harness.Context("cell", 1, 0.0, win.seconds, [0.01] * n,
                          {"nnz_a": 10, "nnz_b": 10, "nnz_c": 10, "n_mult": 10, "itemsize": 4},
                          window=win, spans=spans)
    host = sum(e - s for name, s, e in spans if name in ("bench.pack", "bench.unpack"))
    got = tiny_bench.reader("host_path_ms").read(ctx)
    assert got == pytest.approx(host / 1e6 / n)
    assert got >= 10.0  # 4 + 6 ms of sleep per product
    # the CPU trace has no program line; stand the operations in for it
    win.modules = win.ops
    dev_ns = np.mean([sum(e - s for _, s, e in ops) for ops in win.ops.values()])
    assert tiny_bench.reader("step_device_ms").read(ctx) == pytest.approx(dev_ns / 1e6 / n)
    idle = tiny_bench.reader("device_idle_pct").read(ctx)
    busy = np.mean(list(win.busy_ns.values()))
    assert idle == pytest.approx(100 * (1 - busy / (win.hi - win.lo)))
    assert tiny_bench.reader("all_to_all_ms").read(ctx) is None
    ctx.peaks = {"hbm_bytes_per_s": 1e9}
    share = tiny_bench.reader("step_roofline").read(ctx)
    assert share == pytest.approx(100 * (30 * 4 / 1e9) / (dev_ns / 1e9 / n))
