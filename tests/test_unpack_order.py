"""C's slot -> canonical map (``spgemm_exec._c_order``): built once per plan
and memoized on it, bit-identical to the nonzero-scan reorder it replaced,
invisible to the plan store and the plan fingerprint.

No device is needed: ``owned_c_values`` takes the fetched slot tables as
numpy arrays, so the tests feed it (p, C_max + 1[, b, b]) tables directly.
"""
import numpy as np
import pytest

from repro.api import _plan_one
from repro.checkpoint import restore_plan, save_plan
from repro.core import SpGEMMInstance
from repro.distributed.plan_ir import ExecutionPlan, padded_id_lists
from repro.distributed.runtime import plan_fingerprint
from repro.distributed.spgemm_exec import _canonical_order, owned_c_values
from repro.sparse.structure import random_structure


def _nonzero_scan_order(c_np, plan):
    """The reorder as it was before the map: scan every slot, scatter."""
    local_c = plan.local_ids["c_nz"]
    dev, slot = np.nonzero(local_c >= 0)
    out = np.empty((len(plan.ownership["c_nz"]), *c_np.shape[2:]), c_np.dtype)
    out[local_c[dev, slot]] = c_np[dev, slot]
    return out


def _id_list_plan(p, nnz_c, seed, idle_part=False):
    """A plan whose C slots come from ``padded_id_lists`` over a random
    ownership; ``idle_part`` leaves the last part owning nothing."""
    part = np.random.default_rng(seed).integers(0, p, nnz_c)
    if idle_part:
        part[part == p - 1] = 0
    local_c, _ = padded_id_lists(part, p)
    return ExecutionPlan(
        model="fine", p=p, ownership={"c_nz": part}, local_ids={"c_nz": local_c}
    )


def _slot_tables(plan, block, dtype, seed):
    """Fetched-like slot tables: padding and the sink slot hold values too,
    so reading a wrong slot shows; read-only, as a device fetch can be."""
    c_max = plan.local_ids["c_nz"].shape[1]
    shape = (plan.p, c_max + 1) + ((block, block) if block else ())
    c_np = np.random.default_rng(seed).standard_normal(shape).astype(dtype)
    c_np.flags.writeable = False
    return c_np


def _planned(model, p=2, seed=0):
    rng = np.random.default_rng(seed)
    a = random_structure(30, 26, 0.15, rng)
    b = random_structure(26, 28, 0.15, rng)
    return _plan_one(SpGEMMInstance(a, b), model, p, 0.10, 0, include_nz=False)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("block", [None, 2])
@pytest.mark.parametrize(
    "p,idle_part", [(1, False), (3, False), (3, True), (4, False), (4, True)]
)
def test_reorder_matches_nonzero_scan_bit_for_bit(p, idle_part, block, dtype):
    plan = _id_list_plan(p, 257, seed=p + 10 * idle_part, idle_part=idle_part)
    c_np = _slot_tables(plan, block, dtype, seed=p)
    got = owned_c_values(c_np, plan)
    want = _nonzero_scan_order(c_np, plan)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    # a fresh array of its own, never a view of the fetched buffer
    assert got.flags.owndata and got.flags.writeable
    assert not np.shares_memory(got, c_np)
    # one part owns every id in ascending order, so the p=1 map is arange
    idx = plan._c_order[1]
    assert idx.dtype == np.intp
    assert np.array_equal(idx, np.arange(257)) == (p == 1)
    again = _canonical_order(c_np, idx)
    assert again.tobytes() == want.tobytes() and not np.shares_memory(again, got)


@pytest.mark.parametrize("model", ["fine", "monoC"])
def test_reorder_of_planned_products_matches_nonzero_scan(model):
    plan = _planned(model).execution_plan
    c_np = _slot_tables(plan, 2 if model == "monoC" else None, np.float32, seed=1)
    got = owned_c_values(c_np, plan)
    assert got.tobytes() == _nonzero_scan_order(c_np, plan).tobytes()


@pytest.mark.parametrize("p", [1, 4])
def test_empty_c_gives_empty_values(p):
    plan = _id_list_plan(p, 0, seed=0)
    got = owned_c_values(_slot_tables(plan, None, np.float32, seed=0), plan)
    assert got.shape == (0,) and got.flags.owndata


@pytest.mark.parametrize("fault", ["missing", "repeated", "out_of_range"])
def test_map_that_misses_a_nonzero_raises(fault):
    plan = _id_list_plan(3, 40, seed=2)
    local_c = plan.local_ids["c_nz"]
    held = np.argwhere(local_c >= 0)
    d0, s0 = held[0]
    d1, s1 = held[-1]
    if fault == "missing":
        local_c[d0, s0] = -1
    elif fault == "repeated":
        local_c[d0, s0] = local_c[d1, s1]
    else:
        local_c[d0, s0] = 40
    with pytest.raises(ValueError, match="exactly once"):
        owned_c_values(_slot_tables(plan, None, np.float32, seed=0), plan)
    assert "_c_order" not in plan.__dict__


@pytest.mark.parametrize("p", [1, 3])
def test_map_is_built_once_per_plan(p):
    plan = _id_list_plan(p, 100, seed=5)
    c_np = _slot_tables(plan, None, np.float32, seed=5)
    assert "_c_order" not in plan.__dict__
    first = owned_c_values(c_np, plan)
    memo = plan._c_order
    for _ in range(10):
        assert owned_c_values(c_np, plan).tobytes() == first.tobytes()
    assert plan._c_order is memo


@pytest.mark.parametrize("model", ["fine", "monoC"])
def test_unpack_leaves_the_fingerprint_alone(model):
    plan = _planned(model).execution_plan
    fp = plan_fingerprint(plan)
    plan.__dict__.pop("_fingerprint")
    owned_c_values(_slot_tables(plan, None, np.float32, seed=0), plan)
    assert "_c_order" in plan.__dict__
    assert plan_fingerprint(plan) == fp


@pytest.mark.parametrize("model", ["fine", "monoC"])
def test_store_round_trip_of_a_plan_with_its_map(model, tmp_path):
    """The store ignores the memo; a restored plan builds its own map once
    and gathers the same values."""
    plan = _planned(model).execution_plan
    block = 2 if model == "monoC" else None
    c_np = _slot_tables(plan, block, np.float64, seed=3)
    want = owned_c_values(c_np, plan)
    store = str(tmp_path / "store")
    save_plan(store, f"k_{model}", plan)
    back = restore_plan(store, f"k_{model}").plan
    assert "_c_order" not in back.__dict__
    assert plan_fingerprint(back) == plan_fingerprint(plan)
    assert owned_c_values(c_np, back).tobytes() == want.tobytes()
    memo = back._c_order
    assert memo is not plan._c_order
    for _ in range(3):
        owned_c_values(c_np, back)
    assert back._c_order is memo
    np.testing.assert_array_equal(back._c_order[1], plan._c_order[1])
