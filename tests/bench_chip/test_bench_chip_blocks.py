"""Block-sparse operands: a configuration that declares ``blocks`` is
checked against scipy's product of the scalar expansion, its block shapes
reach the program's session, and a configuration without them runs exactly
as before."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import ml_dtypes
import numpy as np
import pytest
import scipy.sparse as sp
from conftest import ROOT, TINY, cpu_lines

from benchmarks.chip import harness, reference

LIMIT = 1e-4
BLOCKED = "amg27-blocked-n6-fine-p1"
BLOCKED_CELL = BLOCKED + ".closed1"
SCALAR_CELL = "amg27-ap-n72-fine-p1.closed1"
TODAY = {"p", "model", "eps", "seed", "engine", "store_dir", "dtype"}


# ---------------------------------------------------------------------------
# the scalar reference as it was before block operands, kept to compare with
# ---------------------------------------------------------------------------
def scalar_reference(a, b, c_keys, a_vals, b_vals):
    csr_with, at_structure = reference.csr_with, reference.at_structure
    want = at_structure(csr_with(a, a_vals) @ csr_with(b, b_vals), c_keys)
    scale = at_structure(csr_with(a, np.abs(a_vals)) @ csr_with(b, np.abs(b_vals)), c_keys)
    scale[scale == 0] = np.finfo(np.float64).tiny
    return want, scale


def scalar_control(a, b, c_keys, a_vals, b_vals):
    bf16 = lambda x: np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
    return bf16(reference.at_structure(
        reference.csr_with(a, bf16(a_vals)) @ reference.csr_with(b, bf16(b_vals)), c_keys))


def amg27():
    return harness.load_module(ROOT / "benchmarks" / "chip" / "generators" / "amg27.py")


def tiny_pair(name):
    cfg = json.loads((ROOT / "benchmarks" / "chip" / "configs" / f"{name}.json").read_text())
    cfg.update(TINY[name])
    return cfg, *amg27().structures(cfg)


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_scalar_reference_is_as_before(name, seed):
    cfg, a, b = tiny_pair(name)
    pool = amg27().value_pool(cfg, a, b, seed, 2)
    cell = SimpleNamespace(a=a, b=b, pool=pool, config=cfg)
    refs = harness.references(cell, [0, 1])
    for i, ref in refs.items():
        want, scale = scalar_reference(a, b, ref.c_keys, *pool[i])
        assert np.array_equal(ref.want, want) and np.array_equal(ref.scale, scale)
        assert np.array_equal(reference.control(a, b, ref.c_keys, *pool[i]),
                              scalar_control(a, b, ref.c_keys, *pool[i]))


# ---------------------------------------------------------------------------
# the blocked reference
# ---------------------------------------------------------------------------
def random_structure(rng, shape, density=0.4):
    return sp.csr_matrix(rng.random(shape) < density)


def dense(structure, values, block):
    r, c = block
    out = np.zeros((structure.shape[0] * r, structure.shape[1] * c))
    rows = np.repeat(np.arange(structure.shape[0]), np.diff(structure.indptr))
    for t, (i, j) in enumerate(zip(rows, structure.indices)):
        out[i * r:(i + 1) * r, j * c:(j + 1) * c] = values[t]
    return out


def block_major(c_dense, c_keys, n_cols, block):
    r, c = block
    return np.concatenate([c_dense[i * r:(i + 1) * r, j * c:(j + 1) * c].ravel()
                           for i, j in zip(*np.divmod(c_keys, n_cols))])


def blocked_pair(rkc, seed, shape=(7, 5, 6)):
    r, k, c = rkc
    rng = np.random.default_rng(seed)
    a = random_structure(rng, shape[:2])
    b = random_structure(rng, shape[1:])
    a_vals = rng.standard_normal((a.nnz, r, k), dtype=np.float32)
    b_vals = rng.standard_normal((b.nnz, k, c), dtype=np.float32)
    keys = reference.linear_keys(reference.symbolic(a, b))
    return a, b, keys, a_vals, b_vals


@pytest.mark.parametrize("rkc", [(3, 3, 6), (2, 1, 3), (1, 1, 1)])
def test_blocked_reference_is_the_dense_product_block_by_block(rkc):
    r, k, c = rkc
    a, b, keys, a_vals, b_vals = blocked_pair(rkc, 1)
    ref = reference.Reference(a, b, keys, a_vals, b_vals, blocks=((r, k), (k, c)))
    da, db = dense(a, a_vals, (r, k)), dense(b, b_vals, (k, c))
    assert ref.want.shape == (len(keys) * r * c,)
    np.testing.assert_allclose(ref.want, block_major(da @ db, keys, b.shape[1], (r, c)),
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(ref.scale,
                               block_major(np.abs(da) @ np.abs(db), keys, b.shape[1], (r, c)),
                               rtol=0, atol=1e-12)


def test_blocked_reference_reads_an_exact_cancellation_as_zero():
    """A = [1 1] in 1x1 blocks, B's two 1x2 blocks [1 2] and [-1 3]: C's one
    block is [0 5], and scipy stores no 0."""
    a = sp.csr_matrix(np.ones((1, 2), bool))
    b = sp.csr_matrix(np.ones((2, 1), bool))
    keys = reference.linear_keys(reference.symbolic(a, b))
    a_vals = np.ones((2, 1, 1), np.float32)
    b_vals = np.array([[[1, 2]], [[-1, 3]]], np.float32)
    ref = reference.Reference(a, b, keys, a_vals, b_vals, blocks=((1, 1), (1, 2)))
    assert ref.want.tolist() == [0.0, 5.0] and ref.scale.tolist() == [2.0, 5.0]
    assert ref.error(np.array([[[0.0, 5.0]]])) == 0.0


@pytest.mark.parametrize("rkc", [(3, 3, 6), (2, 1, 3)])
def test_blocked_reference_catches_one_entry_and_a_wrong_shape(rkc):
    r, k, c = rkc
    a, b, keys, a_vals, b_vals = blocked_pair(rkc, 2)
    ref = reference.Reference(a, b, keys, a_vals, b_vals, blocks=((r, k), (k, c)))
    got = ref.want.astype(np.float32).astype(np.float64).reshape(len(keys), r, c)
    assert ref.error(got) < LIMIT
    bad = got.copy()
    bad[len(keys) // 2, r - 1, c - 1] += 1e-2 * ref.scale.reshape(got.shape)[len(keys) // 2, r - 1, c - 1]
    assert ref.error(bad) == pytest.approx(1e-2, rel=1e-3)
    assert ref.error(got[:-1]) == float("inf")
    assert ref.error(got[:, :, 0]) == float("inf")


def block_product(a, b, a_vals, b_vals):
    """C = A B of block operands in float32, by numpy alone: every block
    multiplication, scatter-added into C's sorted block keys."""
    if a_vals.ndim == 1:
        a_vals, b_vals = a_vals[:, None, None], b_vals[:, None, None]
    a_rows = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
    counts = np.diff(b.indptr)[a.indices]
    ta = np.repeat(np.arange(a.nnz), counts)
    first = np.repeat(b.indptr[a.indices], counts)
    tb = first + np.arange(len(ta)) - np.repeat(np.cumsum(counts) - counts, counts)
    keys = a_rows[ta].astype(np.int64) * b.shape[1] + b.indices[tb]
    c_keys, slot = np.unique(keys, return_inverse=True)
    out = np.zeros((len(c_keys), a_vals.shape[1], b_vals.shape[2]), np.float32)
    np.add.at(out, slot, np.matmul(a_vals[ta], b_vals[tb]))
    return c_keys, out


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 3])
def test_blocked_control_fails_and_float32_passes(seed):
    """On amg27's n=6 node structure with A 3x3 and P 3x6 blocks."""
    a, b = amg27().structures({"grid": 6, "aggregate": 3, "smoothing_degree": 1})
    rng = np.random.default_rng(seed)
    a_vals = rng.standard_normal((a.nnz, 3, 3), dtype=np.float32)
    b_vals = rng.standard_normal((b.nnz, 3, 6), dtype=np.float32)
    blocks = ((3, 3), (3, 6))
    keys = reference.linear_keys(reference.symbolic(a, b))
    ref = reference.Reference(a, b, keys, a_vals, b_vals, blocks=blocks)
    c_keys, got = block_product(a, b, a_vals, b_vals)
    assert np.array_equal(c_keys, keys)
    assert ref.error(got) < LIMIT
    assert ref.error(reference.control(a, b, keys, a_vals, b_vals, blocks=blocks)) > LIMIT


# ---------------------------------------------------------------------------
# the harness with a blocked cell
# ---------------------------------------------------------------------------
STUB = '''"""amg27's node structures with A in r x k and P in k x c blocks
(``value_blocks``), values standard normal from the seed."""
from pathlib import Path

import numpy as np

from benchmarks.chip.harness import load_module

amg27 = load_module(Path(__file__).with_name("amg27.py"))


def structures(cfg):
    return amg27.structures(cfg)


def value_pool(cfg, a, b, seed, size):
    rng = np.random.default_rng(seed)
    shape_a, shape_b = cfg["value_blocks"]
    return [(rng.standard_normal((a.nnz, *shape_a), dtype=np.float32),
             rng.standard_normal((b.nnz, *shape_b), dtype=np.float32))
            for _ in range(size)]
'''


def add_blocked_cell(bench, blocks=((3, 3), (3, 6)), value_blocks=None):
    """A blocked configuration and its cell, by files and entries alone."""
    d, root = bench.dir, bench.root
    cfg = json.loads((d / "configs" / "amg27-ap-n72-fine-p1.json").read_text())
    cfg.update(name=BLOCKED, generator="blocked_amg27", grid=6,
               value_blocks=value_blocks or blocks)
    if blocks is not None:
        cfg["blocks"] = {"a": list(blocks[0]), "b": list(blocks[1])}
    (d / "configs" / f"{BLOCKED}.json").write_text(json.dumps(cfg))
    (d / "generators" / "blocked_amg27.py").write_text(STUB)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": BLOCKED, "source": "test",
                                "file": f"benchmarks/chip/configs/{BLOCKED}.json",
                                "reduced": ["grid"], "why": "test"})
    manifest["workloads"].append({"name": BLOCKED_CELL, "config": BLOCKED,
                                  "traffic": "closed1", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return harness.Bench.at(root)


class FakeSession:
    """``repro.session`` with the product done by ``block_product``;
    ``fault`` alters one answer or hands every product the first C."""

    calls: list = []
    fault = None

    def __init__(self, **kwargs):
        FakeSession.calls.append(kwargs)
        self.model = kwargs["model"]
        self.events = [SimpleNamespace(kind="restored")]

    def entry_for(self, a, b):
        a, b = a.csr, b.csr
        first = []

        def runtime(a_vals, b_vals):
            import jax.numpy as jnp

            c = block_product(a, b, a_vals, b_vals)[1]
            if FakeSession.fault == "altered":
                c[0, 0, 0] += 1.0
            if FakeSession.fault == "stale":
                first[:] = first or [c]
                c = first[0]
            return jnp.asarray(c)

        c_keys = block_product(a, b, np.ones(a.nnz, np.float32), np.ones(b.nnz, np.float32))[0]
        instance = SimpleNamespace(a=a, b=b, c=SimpleNamespace(nnz=len(c_keys)), n_mult=0)
        exe = SimpleNamespace(pack=lambda x, y: (x, y), runtime=runtime,
                              planned=SimpleNamespace(instance=instance))
        return SimpleNamespace(model=self.model, exe=exe,
                               planned=SimpleNamespace(execution_plan=None))


@pytest.fixture
def fake_session(monkeypatch):
    import repro
    from repro.distributed import spgemm_exec

    monkeypatch.setattr(repro, "session", FakeSession)
    monkeypatch.setattr(spgemm_exec, "owned_c_values", lambda c, plan: np.asarray(c))
    monkeypatch.setattr(FakeSession, "calls", [])
    monkeypatch.setattr(FakeSession, "fault", None)
    return FakeSession


def run_cell(bench, cell, seed):
    return harness.run(bench, cell, seed, 0.3, False, time.perf_counter(),
                       allow_cpu=True, log=lambda m: None, device_lines=cpu_lines)


@pytest.mark.parametrize("seed", [13, 2**31 + 13])
def test_a_blocked_cell_is_checked_block_by_block(tiny_bench, fake_session, seed):
    bench = add_blocked_cell(tiny_bench)
    result = run_cell(bench, BLOCKED_CELL, seed)
    assert result["correct"], result["checks"]
    assert 0 <= result["checks"]["max_err"]["value"] < result["checks"]["max_err"]["limit"]
    assert [c["blocks"] for c in fake_session.calls] == [((3, 3), (3, 6))]
    cell = harness.setup(bench, BLOCKED_CELL, seed, log=lambda m: None)
    sizes = cell.sizes()
    assert sizes["a_block"] == [3, 3] and sizes["b_block"] == [3, 6]
    assert cell.pool[0][0].shape == (sizes["nnz_a"], 3, 3)


@pytest.mark.parametrize("fault", ["altered", "stale"])
def test_a_blocked_cell_with_a_wrong_answer_is_not_correct(tiny_bench, fake_session, fault):
    fake_session.fault = fault
    result = run_cell(add_blocked_cell(tiny_bench), BLOCKED_CELL, 19)
    assert not result["correct"]
    assert result["checks"]["max_err"]["value"] > result["checks"]["max_err"]["limit"]


def test_an_unblocked_cell_calls_the_session_as_before(tiny_bench, fake_session):
    result = run_cell(tiny_bench, SCALAR_CELL, 21)
    assert result["correct"]
    assert [set(c) for c in fake_session.calls] == [TODAY]
    cell = harness.setup(tiny_bench, SCALAR_CELL, 21, log=lambda m: None)
    assert cell.sizes()["a_block"] == [1, 1] and cell.sizes()["b_block"] == [1, 1]


@pytest.mark.parametrize("blocks, value_blocks", [
    (((3, 3), (3, 6)), ((3, 3), (3, 5))),  # P's blocks not as declared
    (((3, 3), (3, 6)), ((), ())),  # scalar values for a blocked configuration
    (None, ((3, 3), (3, 6))),  # block values for a scalar one
])
def test_setup_refuses_values_not_shaped_as_declared(tiny_bench, fake_session,
                                                     blocks, value_blocks):
    bench = add_blocked_cell(tiny_bench, blocks, value_blocks)
    with pytest.raises(ValueError, match="value pool has shapes"):
        harness.setup(bench, BLOCKED_CELL, 1, log=lambda m: None)


@pytest.mark.parametrize("blocks", [{"a": [3, 3], "b": [2, 6]}, {"a": [3, 0], "b": [0, 6]},
                                    {"a": [3], "b": [3, 6]}])
def test_blocks_must_be_r_by_k_and_k_by_c(blocks):
    with pytest.raises(ValueError, match="not r x k and k x c"):
        harness.blocks({"blocks": blocks})


def program_takes_blocks() -> bool:
    import repro

    try:
        repro.session(p=1, model="fine", blocks=((3, 3), (3, 6)))
    except TypeError as exc:
        assert "blocks" in str(exc)
        return False
    return True


def test_a_blocked_cell_on_the_program(tmp_path):
    """A blocked cell on the real program, through the command's own
    ``main``.  A program that does not take ``blocks`` (the parent of the
    change that brings them) fails at once and prints no result, so that a
    blocked cell is measured on the change alone; one that takes them runs
    the cell and comes out correct."""
    from conftest import make_copy, shrink

    root = make_copy(tmp_path / "checkout")
    shrink(root)
    add_blocked_cell(harness.Bench.at(root))
    code = f"""
import runpy, sys
sys.path.insert(0, {str(root)!r})
sys.argv = ["run.py", "--workload", {BLOCKED_CELL!r}, "--seed", "31", "--seconds", "0.3",
            "--trace", "0"]
from benchmarks.chip import harness
run = harness.run
harness.run = lambda *args, **kwargs: run(*args, **kwargs, allow_cpu=True)
runpy.run_path({str(root / 'benchmarks' / 'chip' / 'run.py')!r}, run_name="__main__")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    t = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    seconds = time.perf_counter() - t
    if program_takes_blocks():
        assert out.returncode == 0, out.stderr[-3000:]
        assert json.loads(out.stdout.strip().splitlines()[-1])["correct"]
    else:
        assert out.returncode != 0 and out.stdout.strip() == ""
        assert "unexpected keyword argument 'blocks'" in out.stderr
        assert seconds < 60, seconds

