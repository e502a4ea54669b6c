"""The harness end to end on the CPU at tiny sizes, the command's guards, and
a cell added by files and manifest entries alone."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest
from conftest import ROOT, cpu_lines, make_copy

from benchmarks.chip import harness

CELL = "amg27-ap-n72-fine-p1.closed1"


def run_cell(bench, cell, seed, traced, seconds=0.5):
    return harness.run(bench, cell, seed, seconds, traced, time.perf_counter(),
                       allow_cpu=True, log=lambda m: None, device_lines=cpu_lines)


@pytest.mark.parametrize("seed", [11, 2**31 + 11])
def test_cell_path_end_to_end(tiny_bench, seed):
    result = run_cell(tiny_bench, CELL, seed, traced=False)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in tiny_bench.metrics("end_to_end", CELL)}
    assert set(result["metrics"]) == want
    assert result["metrics"]["product_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    check = result["checks"]["max_err"]
    assert 0 <= check["value"] < check["limit"]
    assert result["device"]["platform"] == "cpu"
    # the plan is in the store now: a second run restores it
    again = run_cell(tiny_bench, CELL, 7, traced=False)
    assert again["correct"]
    assert again["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(tiny_bench):
    result = run_cell(tiny_bench, CELL, 5, traced=True)
    assert result["correct"]
    got = set(result["metrics"])
    assert {"host_path_ms", "device_idle_pct"} <= got
    assert "all_to_all_ms" not in got  # one device: nothing to read
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = result["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and 1 <= len(bd["idle_gaps"]) <= 10


def test_a_cold_run_measures_the_plan_as_restored(tiny_bench):
    """A checkout's first run plans, saves, and then runs what the store
    gives back, as every later run does."""
    first = harness.setup(tiny_bench, CELL, 3, log=lambda m: None)
    assert first.events[:1] == ["cold_replan"]
    assert "saved" in first.events and first.events[-1] == "restored"
    later = harness.setup(tiny_bench, CELL, 3, log=lambda m: None)
    assert later.events == ["restored"]


def test_same_seed_same_values(tiny_bench):
    cell = harness.setup(tiny_bench, CELL, 3, log=lambda m: None)
    other = cell.generator.value_pool(cell.config, cell.a, cell.b, 3, len(cell.pool))
    for (a1, b1), (a2, b2) in zip(cell.pool, other):
        assert (a1 == a2).all() and (b1 == b2).all()


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", CELL, "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_cli_refuses_a_machine_without_a_tpu():
    out = _cli(ARGS, ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    lone = make_copy(tmp_path / "lone")
    (lone / "src").unlink()
    out = _cli(ARGS, lone)
    assert out.returncode != 0 and out.stdout.strip() == ""


PACED = """
import time
from benchmarks.chip.harness import Reservoir, WindowResult


def warm(cell):
    cell.product(*cell.pool[0])


def drive(cell, seconds, seed):
    keep = Reservoir(cell.traffic["check_sample"], seed)
    latencies = []
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        i = len(latencies) % len(cell.pool)
        t = time.perf_counter()
        keep.offer(i, cell.product(*cell.pool[i]))
        latencies.append(time.perf_counter() - t)
        time.sleep(cell.traffic["gap_s"])
    return WindowResult(time.perf_counter() - start, latencies, 0, keep.sample)
"""


def test_a_cell_added_by_files_and_entries_alone(tiny_bench):
    """A configuration, a traffic mix with a loop of its own and a
    per-layer metric that exist only in this copy of the benchmark run
    without an edit to any file there."""
    d, root = tiny_bench.dir, tiny_bench.root
    cfg = json.loads((d / "configs" / "amg27-ap-n72-fine-p1.json").read_text())
    cfg.update(name="amg27-ap-n9-fine-p1", grid=9)
    (d / "configs" / "amg27-ap-n9-fine-p1.json").write_text(json.dumps(cfg))
    (d / "loops" / "paced.py").write_text(PACED)
    (d / "traffic" / "paced-small.json").write_text(json.dumps(
        {"loop": "paced", "gap_s": 0.02, "pool": 2, "check_sample": 1, "why": "test"}))
    (d / "metrics" / "products_in_window.py").write_text(
        "def read(ctx):\n    return ctx.products\n")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "amg27-ap-n9-fine-p1", "source": "test",
                                "file": "benchmarks/chip/configs/amg27-ap-n9-fine-p1.json",
                                "reduced": ["grid"], "why": "test"})
    cell = "amg27-ap-n9-fine-p1.paced-small"
    manifest["workloads"].append({"name": cell, "config": "amg27-ap-n9-fine-p1",
                                  "traffic": "paced-small", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "products_in_window", "unit": "1", "better": "higher",
                                  "source": "host_clock", "layer": "test", "moves": "product_s",
                                  "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    bench = harness.Bench.at(root)
    result = run_cell(bench, cell, 9, traced=True)
    assert result["correct"]
    assert result["metrics"]["products_in_window"]["value"] == result["attempted"]
    plain = run_cell(bench, cell, 9, traced=False)
    assert set(plain["metrics"]) == {"product_s", "setup_s"}
    # the paced loop leaves gap_s between products: fewer of them than the closed loop's
    assert plain["metrics"]["product_s"]["value"] >= 0.02


def test_a_mix_names_a_loop_that_is_not_there(tiny_bench):
    d = tiny_bench.dir
    mix = json.loads((d / "traffic" / "closed1.json").read_text())
    mix["loop"] = "open"
    (d / "traffic" / "closed1.json").write_text(json.dumps(mix))
    with pytest.raises(FileNotFoundError):
        harness.setup(tiny_bench, CELL, 1, log=lambda m: None)


@pytest.mark.parametrize("seed", [4, 2**31 + 4])
def test_the_reservoir_keeps_outputs_drawn_from_the_seed(seed):
    def draw(s):
        keep = harness.Reservoir(3, s)
        for i in range(50):
            keep.offer(i % 4, np.full(2, i))
        assert all(idx == int(out[0]) % 4 for idx, out in keep.sample)
        return sorted(int(out[0]) for _, out in keep.sample)

    got = draw(seed)
    assert got == draw(seed) and len(set(got)) == 3
    # later products are drawn too, not only the first three
    assert any(max(draw(seed + k)) >= 3 for k in range(5))


def test_four_chip_cell_on_four_host_devices(tmp_path):
    """The monoC cell's routed path on four forced host devices, in a child
    process so that the device count does not leak into this one."""
    root = make_copy(tmp_path / "checkout")
    code = f"""
import sys, time, json
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'tests' / 'bench_chip')!r}, {str(ROOT / 'src')!r}]
from conftest import shrink, cpu_lines
from benchmarks.chip import harness
from pathlib import Path
shrink(Path({str(root)!r}))
bench = harness.Bench.at({str(root)!r})
r = harness.run(bench, "amg27-ap-n72-monoC-p4.closed1", 17, 0.5, False, time.perf_counter(),
                allow_cpu=True, log=lambda m: None)
print(json.dumps(r))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"], result
    assert result["device"]["count"] == 4
