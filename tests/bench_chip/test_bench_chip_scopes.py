"""The program's scopes and spans as the benchmark reads them: a synthetic
TPU trace written as an xplane file (two chips, each operation's name stack
in its event metadata's ``tf_op`` stat, as the chip's profiler writes it),
and the CPU traced harness run."""
from __future__ import annotations

import time

import pytest
from conftest import cpu_lines

from benchmarks.chip import harness, scopes, trace

CELL = "amg27-ap-n72-fine-p1.closed1"
SIZES = {"nnz_a": 10, "nnz_b": 10, "nnz_c": 10, "n_mult": 10, "itemsize": 4}

#: (device, op name, tf_op, start_ns, end_ns)
OPS = [
    (0, "fusion.4", "jit(traced)/repro.scatter_values/squeeze:", 50, 130),  # cut at 100
    (0, "fusion", "jit(traced)/repro.local/gather:", 130, 330),
    (0, "sort.1", "jit(traced)/repro.local/scatter-add:", 300, 400),  # overlaps fusion
    (0, "and_reduce_fusion", None, 400, 420),
    (0, "all-to-all.1", "jit(traced)/shard_map/repro.expand_a/all_to_all:", 420, 440),
    (0, "fusion.9", "jit(traced)/shard_map/transpose(repro.reduce_c)/add:", 440, 450),
    (0, "fusion", "jit(traced)/repro.local/gather:", 600, 700),
    (0, "fusion", "jit(traced)/repro.local/gather:", 1200, 1300),  # after the window
    (1, "fusion.4", "jit(traced)/repro.scatter_values/squeeze:", 200, 260),
    (1, "fusion.2", "jit(traced)/repro.local/scatter-add:", 260, 660),
]
#: host spans: two products, each pack / step / unpack with the program's inside
SPANS = [
    ("bench.window", 100, 1100),
    ("bench.pack", 100, 110), ("repro.pack", 102, 108),
    ("bench.step", 110, 460), ("repro.call", 112, 125),
    ("bench.unpack", 460, 590), ("repro.unpack.fetch", 462, 480),
    ("repro.unpack.reorder", 480, 585),
    ("bench.pack", 590, 598), ("repro.pack", 591, 597),
    ("bench.step", 598, 710), ("repro.call", 599, 605),
    ("bench.unpack", 710, 1100), ("repro.unpack.fetch", 712, 730),
    ("repro.unpack.reorder", 730, 1090),
    ("repro.call", 1150, 1160),  # after the window
]


def hlo_text(name: str) -> str:
    return f"%{name} = f32[8]{{0}} {name.split('.')[0].split('_')[-1]}(f32[8]{{0}} %p)"


def xspace(ops=OPS, spans=SPANS, stack_stat="tf_op", by_ref=False) -> bytes:
    """An xplane file in the chip profiler's layout."""
    from jax.profiler import ProfileData

    planes = []
    for dev in sorted({d for d, *_ in ops}):
        names = sorted({(n, tf) for d, n, tf, _, _ in ops if d == dev}, key=str)
        meta_id = {nt: i + 1 for i, nt in enumerate(names)}
        metas, strings = [], {}
        for (name, tf), i in meta_id.items():
            stat = ""
            if tf is not None:
                if by_ref:
                    ref = strings.setdefault(tf, 100 + len(strings))
                    stat = f"stats {{ metadata_id: 1 ref_value: {ref} }}"
                else:
                    stat = f"stats {{ metadata_id: 1 str_value: {tf!r} }}"
            metas.append(f"event_metadata {{ key: {i} value {{ id: {i} name: {hlo_text(name)!r} "
                         f"display_name: {name!r} {stat} }} }}")
        events = "".join(
            f"events {{ metadata_id: {meta_id[(n, tf)]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}"
            for d, n, tf, s, e in ops if d == dev)
        stat_md = [f'stat_metadata {{ key: 1 value {{ id: 1 name: "{stack_stat}" }} }}']
        stat_md += [f"stat_metadata {{ key: {k} value {{ id: {k} name: {tf!r} }} }}"
                    for tf, k in strings.items()]
        module = "events { metadata_id: 999 offset_ps: 0 duration_ps: 2000000 }"
        planes.append(
            f'planes {{ id: {dev + 1} name: "/device:TPU:{dev}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {module} }} '
            f'event_metadata {{ key: 999 value {{ id: 999 name: "jit_traced" }} }} '
            + " ".join(metas + stat_md) + " }")
    host_meta = {name: i + 1 for i, name in enumerate(sorted({n for n, _, _ in spans}))}
    host_events = "".join(
        f"events {{ metadata_id: {host_meta[n]} offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}"
        for n, s, e in spans)
    planes.append(
        f'planes {{ id: 9 name: "/host:CPU" lines {{ id: 1 name: "python3" timestamp_ns: 0 '
        f'{host_events} }} '
        + " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: {n!r} }} }}"
                   for n, i in host_meta.items()) + " }")
    return ProfileData.text_proto_to_serialized_xspace(" ".join(planes))


def write_trace(chip_dir, data: bytes, cell=CELL):
    path = scopes.trace_dir(cell, chip_dir) / "plugins" / "profile" / "t0" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(data)
    return path


def context(path, products=2, cell=CELL):
    rec = trace.load(path)
    win = trace.reduce(rec, harness.WINDOW_SPAN)
    return harness.Context(cell, 2, 0.0, win.seconds, [0.1] * products, SIZES,
                           window=win, spans=trace.clip(rec.spans, win.lo, win.hi))


def union_len(ivs) -> int:
    return len({t for s, e in ivs for t in range(max(s, 100), min(e, 1100))})


def innermost(t: float) -> str:
    """The latest-started span open at ``t``; of two that start together,
    the later one in the trace."""
    found = trace.NO_SPAN
    for name, s, e in sorted(SPANS, key=lambda sp: sp[1]):
        if s <= t < e:
            found = name
    return found


def test_scope_of_name_stacks():
    assert scopes.scope_of("jit(traced)/shard_map/repro.local/mul:") == "repro.local"
    assert scopes.scope_of("jit(f)/transpose(jvp(repro.reduce_c))/add") == "repro.reduce_c"
    assert scopes.scope_of("jit(f)/repro.local/x;repro.expand_a/y") == "repro.expand_a"
    assert scopes.scope_of("jit(f)/notrepro.local/mul") is None
    assert scopes.scope_of(None) is None and scopes.scope_of("") is None


@pytest.mark.parametrize("by_ref", [False, True])
def test_metadata_gives_each_op_its_scope(tmp_path, by_ref):
    data = xspace(by_ref=by_ref)
    named = scopes.scopes_of_metadata(data)
    assert named["/device:TPU:0"][hlo_text("sort.1")] == "repro.local"
    assert named["/device:TPU:0"][hlo_text("fusion.9")] == "repro.reduce_c"
    assert named["/device:TPU:0"][hlo_text("and_reduce_fusion")] is None
    assert named["/device:TPU:1"][hlo_text("fusion.4")] == "repro.scatter_values"
    # two events of one name that disagree on their scope get none
    clash = [(0, "fusion.1", "jit(f)/repro.local/a:", 0, 5), (0, "fusion.1", "jit(f)/repro.expand_a/b:", 5, 9)]
    assert scopes.scopes_of_metadata(xspace(clash))["/device:TPU:0"][hlo_text("fusion.1")] is None


def test_union_within_a_scope_clip_mean_and_per_product(tmp_path):
    path = write_trace(tmp_path, xspace())
    ctx = context(path)
    got = scopes.for_ctx(ctx, tmp_path)
    for dev in ctx.window.ops:  # the same operations as the run's reduction
        assert got.count(dev) == len(ctx.window.ops[dev])

    def want(dev, names):
        return union_len([(s, e) for d, _, tf, s, e in OPS
                          if d == dev and scopes.scope_of(tf) in names])

    local = [want(d, scopes.LOCAL) for d in (0, 1)]
    assert local == [370, 400]  # sort.1 overlaps the gather: counted once
    assert scopes.device_ms(ctx, scopes.LOCAL, tmp_path) == pytest.approx(385 / 1e6 / 2)
    assert [want(d, scopes.SCATTER) for d in (0, 1)] == [30, 60]  # cut to the window
    assert scopes.device_ms(ctx, scopes.SCATTER, tmp_path) == pytest.approx(45 / 1e6 / 2)
    assert scopes.device_ms(ctx, scopes.EXCHANGE, tmp_path) == pytest.approx(15 / 1e6 / 2)
    # the scopes and the unscoped operations together are the busy time
    busy = trace.mean(ctx.window.busy_ns)
    scoped = trace.mean(got.scope_ns([sc for sc in got.all_scopes() if sc]))
    unscoped = trace.mean(got.scope_ns([None]))
    assert unscoped == 10  # and_reduce_fusion on one chip of two
    assert scoped + unscoped == busy


def test_host_spans_per_product(tmp_path):
    ctx = context(write_trace(tmp_path, xspace()))
    for span, ns in [("repro.pack", 12), ("repro.call", 19), ("repro.unpack.fetch", 36),
                     ("repro.unpack.reorder", 465)]:
        assert scopes.host_ms(ctx, span, tmp_path) == pytest.approx(ns / 1e6 / 2), span


def test_idle_by_program_span(tmp_path):
    path = write_trace(tmp_path, xspace())
    got = scopes.load(path).window(100, 1100)
    idle = got.idle_by_program_span(100, 1100)
    want: dict[str, float] = {}
    for dev in (0, 1):
        busy = {t for d, _, _, s, e in OPS if d == dev for t in range(max(s, 100), min(e, 1100))}
        for t in range(100, 1100):
            if t not in busy:
                name = innermost(t + 0.5)
                want[name] = want.get(name, 0) + 0.5
    assert idle == pytest.approx(want)
    assert max(idle, key=idle.get) == "repro.unpack.reorder"


def test_summary_of_a_run(tmp_path):
    path = write_trace(tmp_path, xspace())
    s = scopes.summary(path)
    assert s["products"] == 2
    assert s["scopes_ms"]["repro.local"] == pytest.approx(385 / 1e6 / 2)
    assert s["scopes_ms"][scopes.UNSCOPED] == pytest.approx(10 / 1e6 / 2)
    assert s["scoped_share"] == pytest.approx(1 - 10 / ((450 + 460) / 2))
    assert s["unscoped_top_ms"][0][0] == "and_reduce_fusion (fusion)"
    assert s["spans_ms"]["repro.unpack.reorder"] == pytest.approx(465 / 1e6 / 2)


def test_readers_in_a_checkout(tiny_bench):
    """The seven readers find the trace from their own checkout."""
    write_trace(tiny_bench.dir, xspace(), cell="amg27-ap-n72-monoC-p4.closed1")
    ctx = context(scopes.trace_dir("amg27-ap-n72-monoC-p4.closed1", tiny_bench.dir)
                  / "plugins" / "profile" / "t0" / "host.xplane.pb",
                  cell="amg27-ap-n72-monoC-p4.closed1")
    got = harness.read_metrics(tiny_bench, "per_layer", ctx)
    want = {"scatter_device_ms": 45, "local_device_ms": 385, "exchange_device_ms": 15,
            "pack_host_ms": 12, "call_host_ms": 19, "unpack_fetch_ms": 36,
            "unpack_reorder_ms": 465}
    for name, ns in want.items():
        assert got[name]["value"] == pytest.approx(ns / 1e6 / 2), name
        assert got[name]["unit"] == "ms"


def test_a_program_without_scopes_or_spans_reads_nothing(tiny_bench):
    """The parent of this instrumentation: no ``tf_op`` scope, no
    ``repro.`` span.  Every new reader returns None, and none raises."""
    bare = [(d, n, None, s, e) for d, n, _, s, e in OPS]
    spans = [sp for sp in SPANS if sp[0].startswith("bench.")]
    path = write_trace(tiny_bench.dir, xspace(bare, spans))
    ctx = context(path)
    for name in ("scatter_device_ms", "local_device_ms", "exchange_device_ms", "pack_host_ms",
                 "call_host_ms", "unpack_fetch_ms", "unpack_reorder_ms"):
        assert tiny_bench.reader(name).read(ctx) is None, name
    ctx.window = None
    assert tiny_bench.reader("local_device_ms").read(ctx) is None


def test_a_reduction_that_disagrees_is_refused(tmp_path):
    ctx = context(write_trace(tmp_path, xspace()))
    dev = next(iter(ctx.window.ops))
    ctx.window.ops[dev] = ctx.window.ops[dev][1:]
    with pytest.raises(ValueError, match="operations in the window"):
        scopes.device_ms(ctx, scopes.LOCAL, tmp_path)


def test_one_parse_per_file(tmp_path, monkeypatch):
    path = write_trace(tmp_path, xspace())
    first = scopes.load(path)
    monkeypatch.setattr(scopes, "_parse", lambda *a: pytest.fail("parsed twice"))
    assert scopes.load(path) is first


def test_traced_cpu_run_reports_the_host_spans_inside_the_benchmarks(tiny_bench):
    """On the CPU the program's host spans are read; each lies inside the
    benchmark span that wraps its call.  (A CPU trace carries no name
    stack, so the device scopes read nothing here.)"""
    result = harness.run(tiny_bench, CELL, 9, 0.5, True, time.perf_counter(), allow_cpu=True,
                         log=lambda m: None, device_lines=cpu_lines)
    assert result["correct"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    host = ("pack_host_ms", "call_host_ms", "unpack_fetch_ms", "unpack_reorder_ms")
    assert set(host) <= set(got), got
    assert not {"scatter_device_ms", "local_device_ms"} & set(got)
    path = trace.xplane_file(tiny_bench.trace_dir(CELL))
    whole = scopes.load(path)
    (lo, hi), = [(s, e) for n, s, e in whole.spans if n == harness.WINDOW_SPAN]
    win = whole.window(lo, hi)
    n = result["attempted"]
    bench_ms = {name: win.span_ns(name) / 1e6 / n
                for name in ("bench.pack", "bench.step", "bench.unpack")}
    assert 0 < got["pack_host_ms"] <= bench_ms["bench.pack"]
    assert 0 < got["call_host_ms"] <= bench_ms["bench.step"]
    assert 0 < got["unpack_fetch_ms"] + got["unpack_reorder_ms"] <= bench_ms["bench.unpack"]
    assert got["host_path_ms"] == pytest.approx(bench_ms["bench.pack"] + bench_ms["bench.unpack"])
