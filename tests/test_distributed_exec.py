"""Multi-device executor tests (subprocess: needs 4 placeholder devices,
which must not leak into this pytest process' jax)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNER = os.path.join(ROOT, "tests", "multidev_runner.py")


def _run(case: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_DEVICES"] = str(devices)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, RUNNER, case],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.parametrize(
    "case", ["rowwise", "outer", "columnwise", "rowwise_identity_partition"]
)
def test_distributed_spgemm(case):
    assert f"OK {case.split('_partition')[0]}" in _run(case)


@pytest.mark.parametrize("devices", [4, 8])
@pytest.mark.parametrize("case", ["monoC", "monoC_blocked"])
def test_monoC_spgemm_matches_dense_oracle(case, devices):
    """2 instances x p in {4, 8}: the 2D monochrome-C executor equals A @ B."""
    assert f"OK {case} p={devices}" in _run(case, devices=devices)


def test_monoC_identity_partition_has_zero_traffic():
    assert "OK monoC_identity" in _run("monoC_identity_partition")


@pytest.mark.parametrize("devices", [4, 8])
@pytest.mark.parametrize("case", ["fine", "fine_nz"])
def test_fine_spgemm_matches_dense_oracle(case, devices):
    """3D fine-grained executor == A @ B at p in {4, 8}, with the planned
    words pinned to the fine hypergraph's connectivity cost."""
    assert f"OK {case} p={devices}" in _run(case, devices=devices)


def test_fine_identity_partition_has_zero_traffic():
    assert "OK fine_identity" in _run("fine_identity_partition")


@pytest.mark.parametrize("devices", [4, 8])
def test_model_selection_sweep_end_to_end(devices):
    """sweep_instance: all models partitioned, executors run, and measured
    == predicted words for every plan."""
    assert "OK select best=" in _run("select", devices=devices)


def test_compressed_psum_error_feedback():
    assert "OK compressed_psum" in _run("compressed_psum")


def test_moe_expert_parallel_matches_fallback():
    """shard_map EP dispatch == single-device dispatch (no-drop capacity)."""
    assert "OK moe_ep" in _run("moe_ep")
