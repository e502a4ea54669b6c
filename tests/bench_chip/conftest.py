"""Shared pieces of the chip benchmark's tests, which run on the CPU.

``tiny_bench`` copies the benchmark (``BENCHMARK.json`` and
``benchmarks/chip``) into a temporary checkout whose ``src`` is this
repository's, with the configurations cut to a few hundred rows, so that
the harness runs every cell's path in seconds.  ``cpu_lines`` tells the
trace reduction which lines of a CPU trace hold XLA's work, in place of a
TPU's device planes.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

#: configuration keys cut so that each cell's path runs in seconds here
TINY = {
    "amg27-ap-n72-fine-p1": {"grid": 6},
    "amg27-ap-n72-monoC-p4": {"grid": 6},
}


def cpu_lines(plane: str, line: str) -> str | None:
    """XLA's CPU client threads run the programs' operations."""
    if plane == "/host:CPU" and line.startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen")):
        return "XLA Ops"
    return None


def make_copy(dest: Path) -> Path:
    """A checkout holding the benchmark's files and a link to ``src``."""
    (dest / "benchmarks").mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "chip", dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".store", ".cache", ".traces", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "src").symlink_to(ROOT / "src")
    return dest


def shrink(root: Path, cuts=TINY) -> None:
    for name, cut in cuts.items():
        path = root / "benchmarks" / "chip" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(cut)
        path.write_text(json.dumps(cfg))


@pytest.fixture
def tiny_bench(tmp_path):
    from benchmarks.chip import harness

    root = make_copy(tmp_path / "checkout")
    shrink(root)
    return harness.Bench.at(root)


@pytest.fixture(autouse=True)
def jax_config_restored():
    """The harness turns JAX's persistent compilation cache on in its own
    checkout; put the process's settings back after each test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
