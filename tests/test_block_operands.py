"""Block-sparse operands through the front door: every nonzero of A an r x k
block and of B a k x c block, planned and run by the fine executor family.

The reference is scipy's float64 product of the operands' scalar expansions
(``bsr_matrix(...).tocsr()``), read at C's positions block by block; the
error is componentwise, max |c - ref| / (|A| |B|), as the chip benchmark
reads it."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

import repro
from repro.core.matrices import amg_instances
from repro.distributed.plan_ir import as_blocks
from repro.distributed.runtime import plan_fingerprint
from repro.distributed.spgemm_exec import owned_c_values
from repro.sparse.structure import random_structure

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 1e-4
ELASTIC = ((3, 3), (3, 6))


def expand(structure, values, block) -> sp.csr_matrix:
    """The scalar CSR of block values on a block structure, in float64."""
    csr = structure.csr
    return sp.bsr_matrix(
        (np.asarray(values, np.float64), csr.indices, csr.indptr),
        shape=(csr.shape[0] * block[0], csr.shape[1] * block[1]),
    ).tocsr()


def blocks_of(dense, c_structure, block) -> np.ndarray:
    """C's (nnz, r, c) blocks out of a dense scalar array, canonical order."""
    r, c = block
    rows, cols = c_structure.coo()
    out = dense.reshape(dense.shape[0] // r, r, dense.shape[1] // c, c)
    return out[rows, :, cols, :]


def error(got, a_s, b_s, c_s, a_vals, b_vals, blocks) -> float:
    (r, _), (_, c) = blocks
    a64, b64 = expand(a_s, a_vals, blocks[0]), expand(b_s, b_vals, blocks[1])
    want = blocks_of((a64 @ b64).toarray(), c_s, (r, c))
    scale = blocks_of((abs(a64) @ abs(b64)).toarray(), c_s, (r, c))
    assert got.shape == want.shape
    return float((np.abs(got - want) / np.maximum(scale, np.finfo(float).tiny)).max())


def values(structure, block, rng) -> np.ndarray:
    return rng.standard_normal((structure.nnz, *block), dtype=np.float32)


def product(handle, a_vals, b_vals) -> np.ndarray:
    """pack -> the compiled step -> owned_c_values, the benchmark's path."""
    exe = handle.compile()
    return owned_c_values(exe.runtime(*exe.pack(a_vals, b_vals)), handle.execution_plan)


def amg6():
    inst, _ = amg_instances(6)
    return inst.a, inst.b


def odd_pair(rng):
    return random_structure(9, 7, 0.3, rng), random_structure(7, 8, 0.3, rng)


@pytest.mark.parametrize(
    "operands, blocks, seed",
    [
        ("amg6", ELASTIC, 0),
        ("amg6", ELASTIC, 2**31 + 1),
        ("odd", ((2, 3), (3, 1)), 2),
    ],
)
def test_fine_step_matches_the_scalar_expansion(operands, blocks, seed):
    rng = np.random.default_rng(seed)
    a_s, b_s = amg6() if operands == "amg6" else odd_pair(rng)
    handle = repro.plan(a_s, b_s, p=1, model="fine", blocks=blocks)
    a_vals, b_vals = values(a_s, blocks[0], rng), values(b_s, blocks[1], rng)
    got = product(handle, a_vals, b_vals)
    (r, _), (_, c) = blocks
    assert got.shape == (handle.instance.c.nnz, r, c) and got.dtype == np.float32
    assert error(got, a_s, b_s, handle.instance.c, a_vals, b_vals, blocks) <= LIMIT
    # the front door's dense C is the same product
    dense = handle(a_vals, b_vals)
    want = (expand(a_s, a_vals, blocks[0]) @ expand(b_s, b_vals, blocks[1])).toarray()
    assert dense.shape == want.shape
    np.testing.assert_allclose(dense, want, rtol=1e-4, atol=1e-4)


def test_one_by_one_blocks_are_the_scalar_path():
    a_s, b_s = amg6()
    rng = np.random.default_rng(3)
    a_vals = rng.standard_normal(a_s.nnz, dtype=np.float32)
    b_vals = rng.standard_normal(b_s.nnz, dtype=np.float32)
    scalar = repro.plan(a_s, b_s, p=1, model="fine")
    ones = repro.plan(a_s, b_s, p=1, model="fine", blocks=((1, 1), (1, 1)))
    assert plan_fingerprint(ones.execution_plan) == plan_fingerprint(scalar.execution_plan)
    assert ones.compile().runtime is scalar.compile().runtime
    got = product(ones, a_vals, b_vals)
    assert got.shape == (scalar.instance.c.nnz,)
    assert np.array_equal(got, product(scalar, a_vals, b_vals))


def test_block_area_net_costs_and_route_words():
    a_s, b_s = amg6()
    scalar = repro.plan(a_s, b_s, p=2, model="fine")
    blocked = repro.plan(a_s, b_s, p=2, model="fine", blocks=ELASTIC)
    kinds = scalar.hypergraph.net_kind
    areas = np.array([0, 9, 18, 18])[kinds]
    assert np.array_equal(blocked.hypergraph.net_cost, scalar.hypergraph.net_cost * areas)
    routes = blocked.execution_plan.routes
    assert [routes[n].word_size for n in ("expand_a", "expand_b", "reduce_c")] == [9, 18, 18]
    report = blocked.cost_report()
    assert report["planned_words"] == report["predicted_words"] > 0


def test_scalar_and_blocked_plans_differ_in_fingerprint_and_store(tmp_path):
    a_s, b_s = amg6()
    plans = [repro.plan(a_s, b_s, p=1, model="fine", blocks=b) for b in (None, ELASTIC)]
    assert plan_fingerprint(plans[0].execution_plan) != plan_fingerprint(plans[1].execution_plan)
    blocked = repro.session(p=1, model="fine", store_dir=str(tmp_path), blocks=ELASTIC)
    blocked.entry_for(a_s, b_s)
    scalar = repro.session(p=1, model="fine", store_dir=str(tmp_path))
    entry = scalar.entry_for(a_s, b_s)
    assert [e.kind for e in scalar.events] == ["cold_replan", "saved"]
    assert entry.planned.execution_plan.blocks == ((1, 1), (1, 1))
    assert len(os.listdir(tmp_path)) == 2


def test_a_blocked_session_stores_restores_and_multiplies(tmp_path):
    a_s, b_s = amg6()
    rng = np.random.default_rng(5)
    a_vals, b_vals = values(a_s, ELASTIC[0], rng), values(b_s, ELASTIC[1], rng)
    first = repro.session(p=1, model="fine", store_dir=str(tmp_path), blocks=ELASTIC)
    entry = first.entry_for(a_s, b_s)
    want = product(entry.planned, a_vals, b_vals)
    again = repro.session(p=1, model="fine", store_dir=str(tmp_path), blocks=[[3, 3], [3, 6]])
    back = again.entry_for(a_s, b_s)
    assert [e.kind for e in first.events] == ["cold_replan", "saved"]
    assert [e.kind for e in again.events] == ["restored"]
    plan = back.planned.execution_plan
    assert plan.blocks == ELASTIC
    assert plan_fingerprint(plan) == plan_fingerprint(entry.planned.execution_plan)
    got = owned_c_values(back.exe.runtime(*back.exe.pack(a_vals, b_vals)), plan)
    assert np.array_equal(got, want)
    assert error(got, a_s, b_s, back.planned.instance.c, a_vals, b_vals, ELASTIC) <= LIMIT


@pytest.mark.parametrize("model", ["rowwise", "columnwise", "outer", "monoC", "summa2d", "auto"])
def test_models_off_the_fine_executor_refuse_blocks_by_name(model):
    a_s, b_s = amg6()
    with pytest.raises(ValueError, match=f"model '{model}' does not take block operands"):
        repro.plan(a_s, b_s, p=2, model=model, blocks=ELASTIC)
    with pytest.raises(ValueError, match=f"model '{model}' does not take block operands"):
        repro.session(p=2, model=model, blocks=ELASTIC)


@pytest.mark.parametrize("blocks", [((3, 3), (2, 6)), ((3, 0), (0, 6)), ((3,), (3, 6)), 3])
def test_blocks_must_be_r_by_k_and_k_by_c(blocks):
    with pytest.raises(ValueError, match="are not"):
        as_blocks(blocks)


def test_four_devices_words_weighted_by_block_area():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), REPRO_DEVICES="4")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "multidev_runner.py"), "fine_blocked"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    for model in ("fine", "monoA", "monoB"):
        assert f"OK fine_blocked {model} p=4" in out.stdout
