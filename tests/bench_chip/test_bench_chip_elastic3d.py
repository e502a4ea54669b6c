"""The 3D-elasticity cell: its generator, its two readers, and the cell end
to end on the CPU through the harness against the program, at a few
hundred nodes."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest
from conftest import ROOT, cpu_lines, make_copy, shrink
from test_bench_chip_scopes import OPS, SPANS, context, write_trace, xspace

from benchmarks.chip import harness

NAME = "elastic3d-ap-n72-fine-p1"
CELL = NAME + ".closed1"
CHIP = ROOT / "benchmarks" / "chip"
#: 6^3 nodes in place of 72^3
TINY = {NAME: {"grid": 6}}


def config():
    return json.loads((CHIP / "configs" / f"{NAME}.json").read_text())


def generator(name):
    return harness.load_module(CHIP / "generators" / f"{name}.py")


@pytest.fixture
def elastic_bench(tmp_path):
    root = make_copy(tmp_path / "checkout")
    shrink(root, TINY)
    return harness.Bench.at(root)


@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_generator_gives_amg27s_structures_and_the_declared_blocks(seed):
    cfg = dict(config(), grid=6)
    a, b = generator("elastic3d").structures(cfg)
    a27, b27 = generator("amg27").structures(cfg)
    for mine, theirs in ((a, a27), (b, b27)):
        assert (mine != theirs).nnz == 0 and mine.shape == theirs.shape
    pool = generator("elastic3d").value_pool(cfg, a, b, seed, 2)
    assert harness.blocks(cfg) == ((3, 3), (3, 6))
    for a_vals, b_vals in pool:
        assert a_vals.shape == (a.nnz, 3, 3) and b_vals.shape == (b.nnz, 3, 6)
        assert a_vals.dtype == b_vals.dtype == np.float32
    again = generator("elastic3d").value_pool(cfg, a, b, seed, 2)
    assert all((x == y).all() for pair, other in zip(pool, again) for x, y in zip(pair, other))
    assert not (pool[0][0] == pool[1][0]).all()


def test_configuration_counts_blocks_and_their_scalars():
    cfg = config()
    sizes, n = cfg["sizes"], cfg["grid"]
    assert sizes["nodes"] == n**3 and cfg["reduced"] == {} and cfg["check"]["max_err"] == 1e-4
    assert sizes["scalars_a"] == sizes["nnz_a"] * 9
    assert sizes["scalars_b"] == sizes["nnz_b"] * 18
    assert sizes["scalars_c"] == sizes["nnz_c"] * 18
    assert sizes["multiply_adds"] == sizes["multiplications"] * 3 * 3 * 6
    amg = json.loads((CHIP / "configs" / "amg27-ap-n72-fine-p1.json").read_text())
    for key in ("grid", "aggregate", "smoothing_degree", "model", "p", "eps", "engine"):
        assert cfg[key] == amg[key], key


SIZES = {"nnz_a": 10, "nnz_b": 20, "nnz_c": 30, "n_mult": 40, "itemsize": 4,
         "a_block": [3, 3], "b_block": [3, 6]}


def roofline_context(tmp_path, sizes):
    ctx = context(write_trace(tmp_path, xspace(), cell=CELL), cell=CELL)
    ctx.sizes = sizes
    ctx.peaks = {"hbm_bytes_per_s": 1e9}
    return ctx


@pytest.mark.parametrize("blocks, items", [(([3, 3], [3, 6]), 10 * 9 + 20 * 18 + 30 * 18),
                                           (([1, 1], [1, 1]), 10 + 20 + 30),
                                           (([2, 3], [3, 1]), 10 * 6 + 20 * 3 + 30 * 2)])
def test_block_step_roofline_counts_every_item_of_every_block(tmp_path, blocks, items):
    bench = harness.Bench.at(ROOT)
    sizes = dict(SIZES, a_block=blocks[0], b_block=blocks[1])
    ctx = roofline_context(tmp_path, sizes)
    step_s = bench.reader("step_device_ms").read(ctx) / 1e3
    least = items * 4 / 1e9 / ctx.chips
    assert bench.reader("block_step_roofline").read(ctx) == pytest.approx(100 * least / step_s)
    if blocks == ([1, 1], [1, 1]):  # the scalar share, as step_roofline reads it
        assert bench.reader("block_step_roofline").read(ctx) == pytest.approx(
            bench.reader("step_roofline").read(ctx))
    ctx.peaks = None
    assert bench.reader("block_step_roofline").read(ctx) is None


def test_block_layout_ms_reads_its_scope(elastic_bench):
    layout = [(0, "copy.1", "jit(traced)/repro.block_layout/reshape:", 120, 130),
              (0, "copy.4", "jit(traced)/repro.block_layout/transpose:", 440, 446),
              (1, "copy.1", "jit(traced)/repro.block_layout/reshape:", 100, 120)]
    write_trace(elastic_bench.dir, xspace(OPS + layout), cell=CELL)
    path = next((elastic_bench.dir / ".traces" / CELL).rglob("*.xplane.pb"))
    ctx = context(path, cell=CELL)
    got = harness.read_metrics(elastic_bench, "per_layer", ctx)
    # union per chip: chip 0's 10 + 6 (its copy.4 overlaps all_to_all and
    # fusion.9, not another block_layout operation), chip 1's 20
    assert got["block_layout_ms"]["value"] == pytest.approx((16 + 20) / 2 / 1e6 / 2)
    assert got["block_layout_ms"]["unit"] == "ms"


def test_block_layout_ms_reads_nothing_without_the_scope(elastic_bench):
    write_trace(elastic_bench.dir, xspace(OPS, SPANS), cell=CELL)
    path = next((elastic_bench.dir / ".traces" / CELL).rglob("*.xplane.pb"))
    ctx = context(path, cell=CELL)
    assert elastic_bench.reader("block_layout_ms").read(ctx) is None


def run_cell(bench, seed, traced):
    return harness.run(bench, CELL, seed, 0.5, traced, time.perf_counter(), allow_cpu=True,
                       log=lambda m: None, device_lines=cpu_lines)


@pytest.mark.parametrize("seed", [23, 2**31 + 23])
def test_the_cell_end_to_end_on_the_program(elastic_bench, seed):
    result = run_cell(elastic_bench, seed, traced=False)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    check = result["checks"]["max_err"]
    assert 0 <= check["value"] < check["limit"]
    assert set(result["metrics"]) == {"product_s", "setup_s"}
    cell = harness.setup(elastic_bench, CELL, seed, log=lambda m: None)
    assert cell.events == ["restored"]
    assert cell.plan.blocks == ((3, 3), (3, 6))
    sizes = cell.sizes()
    assert sizes["a_block"] == [3, 3] and sizes["b_block"] == [3, 6]
    c = cell.product(*cell.pool[0])
    assert c.shape == (sizes["nnz_c"] * 18,)


def test_the_traced_cell_reports_the_host_spans(elastic_bench):
    """A CPU trace carries no name stack, no program line and no peak
    table, so the device step readers read nothing here; the host spans
    and the device's idle share are read."""
    result = run_cell(elastic_bench, 29, traced=True)
    assert result["correct"]
    got = set(result["metrics"])
    assert {"host_path_ms", "pack_host_ms", "call_host_ms", "unpack_fetch_ms",
            "unpack_reorder_ms", "device_idle_pct"} <= got
    assert not {"block_layout_ms", "block_step_roofline", "step_roofline"} & got
