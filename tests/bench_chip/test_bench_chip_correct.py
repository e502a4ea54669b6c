"""What decides ``correct``: the control fails the check and the program
passes it, and a run whose timed path is broken underneath comes out not
correct, once for each fault a cell can have."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
from conftest import ROOT, cpu_lines, make_copy

from benchmarks.chip import calibrate, harness, reference

CELL = "amg27-ap-n72-fine-p1.closed1"


@pytest.fixture(autouse=True)
def fresh_executors():
    """Faults are planted at compile time: no executor may come from the
    process-wide cache of an earlier test."""
    from repro.distributed import runtime

    runtime.cache_clear()
    yield
    runtime.cache_clear()


@pytest.mark.parametrize("seeds", [(101, 102, 103), (2**31 + 101, 2**31 + 102, 2**31 + 103)])
def test_control_fails_and_program_passes(tiny_bench, seeds):
    state = harness.setup(tiny_bench, CELL, 1, log=lambda m: None)
    limit = state.config["check"]["max_err"]
    for seed in seeds:
        got = calibrate.readings(state, seed, 0.2)
        assert got["sampled"] >= 1
        assert got["program"] < limit < got["control"], got


def test_reference_error_is_componentwise():
    import scipy.sparse as sp

    a = sp.csr_matrix(np.array([[1, 1, 0], [0, 1, 1]], bool))
    b = sp.csr_matrix(np.array([[1, 0], [1, 1], [0, 1]], bool))
    keys = reference.linear_keys(reference.symbolic(a, b))
    a_vals = np.array([1.0, -1.0, 1e-3, 1e-3], np.float32)
    b_vals = np.array([2.0, 2.0, 1.0, 1.0], np.float32)
    ref = reference.Reference(a, b, keys, a_vals, b_vals)
    want = ref.want.copy()
    assert ref.error(want) == 0.0
    # a small entry off by its own size reads 1, however large the others are
    bad = want.copy()
    bad[-1] *= 2
    assert ref.error(bad) == pytest.approx(1.0)
    assert ref.error(want[:-1]) == float("inf")
    assert ref.error(np.where(np.arange(len(want)) == 0, np.nan, want)) == float("inf")


def broken_run(bench, cell):
    return harness.run(bench, cell, 23, 0.3, False, time.perf_counter(),
                       allow_cpu=True, log=lambda m: None, device_lines=cpu_lines)


def test_an_answer_altered_where_it_is_produced(tiny_bench, monkeypatch):
    from repro.distributed import spgemm_exec

    make = spgemm_exec.make_fine_step

    def altered(*args, **kwargs):
        step, tables = make(*args, **kwargs)
        return (lambda *xs: step(*xs).at[0, 0].add(1.0)), tables

    monkeypatch.setattr(spgemm_exec, "make_fine_step", altered)
    result = broken_run(tiny_bench, CELL)
    assert not result["correct"]
    assert result["checks"]["max_err"]["value"] > result["checks"]["max_err"]["limit"]


def test_a_step_that_returns_a_stale_answer(tiny_bench, monkeypatch):
    """Every product after the first gets the first one's C."""
    from repro.distributed import runtime

    call = runtime.CompiledSpGEMM.__call__
    first = {}

    def stale(self, a, b):
        if id(self) not in first:
            first[id(self)] = np.asarray(call(self, a, b))
        import jax.numpy as jnp

        return jnp.asarray(first[id(self)])

    monkeypatch.setattr(runtime.CompiledSpGEMM, "__call__", stale)
    assert not broken_run(tiny_bench, CELL)["correct"]


def test_the_exchange_between_chips_left_out(tmp_path):
    """The four-chip cell with every all_to_all replaced by zeros, on four
    forced host devices in a child process."""
    root = make_copy(tmp_path / "checkout")
    code = f"""
import sys, time, json
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'tests' / 'bench_chip')!r}, {str(ROOT / 'src')!r}]
from pathlib import Path
import jax, jax.numpy as jnp
from conftest import shrink
from benchmarks.chip import harness
from repro.distributed import spgemm_exec
spgemm_exec.jax.lax.all_to_all = lambda x, *a, **k: jnp.zeros_like(x)
shrink(Path({str(root)!r}))
bench = harness.Bench.at({str(root)!r})
r = harness.run(bench, "amg27-ap-n72-monoC-p4.closed1", 29, 0.3, False, time.perf_counter(),
                allow_cpu=True, log=lambda m: None)
print(json.dumps(r))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert not result["correct"], result
