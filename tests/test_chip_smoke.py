"""``chip_smoke.py`` on the CPU: its phases at tiny sizes, and its guards.

The script itself only runs on a TPU; here each phase function runs with
the Pallas kernels in interpret mode, the four-chip phase on four forced
host devices in a child process, and the platform guard is checked to
refuse the CPU.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import warnings
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    env.update(extra)
    return env


def test_guard_refuses_cpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=_child_env(),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert "needs a TPU, found platform 'cpu'" in out.stdout
    assert '"ok"' not in out.stdout


def test_guard_fails_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = _child_env()
    env.pop("PYTHONPATH")
    out = subprocess.run(
        [sys.executable, str(lone)],
        env=env,
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_partition_fallback_warning_fails_the_run(smoke):
    import importlib

    partition_mod = importlib.import_module("repro.core.partition")
    with smoke.fallbacks_are_errors():
        with pytest.raises(RuntimeWarning, match="falling back"):
            warnings.warn("device coarsening unavailable; falling back", RuntimeWarning)
        partition_mod._FALLBACK_WARNED.discard("smoke_probe")
        with pytest.raises(RuntimeWarning, match="falling back"):
            partition_mod._warn_fallback("smoke_probe", "falling back to engine='flat'")
    partition_mod._FALLBACK_WARNED.discard("smoke_probe")


def test_kernel_phase_tiny(smoke):
    rec = smoke.phase_kernel(6, 8)
    assert rec["mode"] == "interpret" and rec["pairs"] > 0


def test_library_phase_tiny(smoke):
    rec = smoke.phase_library(6, calls=2)
    assert set(rec) == {"fine/platform", "monoC/xla", "monoC/interpret"}


def test_partitioner_phase_tiny(smoke, monkeypatch):
    import importlib

    # let the device engine run on an instance this small
    monkeypatch.setattr(importlib.import_module("repro.core.partition"), "DEVICE_MIN_VERTICES", 0)
    rec = smoke.phase_partitioner(6, 4)
    assert rec["device"]["max_load_over_cap"] <= 1.0


def test_server_phase_tiny(smoke):
    rec = smoke.phase_server(0.02, 4, max_batch=2)
    assert rec["report"]["completed"] == 4 and rec["report"]["failed"] == 0


def test_routed_phase_on_four_host_devices():
    code = "import chip_smoke; chip_smoke.phase_routed(6, 4)"
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    for model in ("fine", "monoC", "summa2d"):
        assert f'"model": "{model}", "p": 4' in out.stdout
