"""The fine step's segmented sum: slot-sorted products summed per produced
slot by shift-and-add passes, the owned C table gathered from the run heads.

Checked against ``np.add.at`` on sorted segment ids, against the step as it
was before (scatter-add into a produced table, then a scatter-add fold
through ``prod_to_owned``, kept below as ``scatter_step``) and against
scipy's float64 product; and, in the compiled p=1 program, that no scatter
is left under ``repro.local``.
"""
from __future__ import annotations

import re

import numpy as np
import pytest
import scipy.sparse as sp

import repro
from repro.core.matrices import amg_instances
from repro.distributed.plan_ir import SCALAR_BLOCKS, build_fine_plan
from repro.distributed.spgemm_exec import _segment_heads, owned_c_values
from repro.sparse.structure import from_dense, random_structure
from test_named_scopes import INSTR, SCOPE

ELASTIC = ((3, 3), (3, 6))
TOL = 1e-6


@pytest.fixture(scope="module")
def amg6():
    inst, _ = amg_instances(6)
    return inst.a, inst.b


def passes_for(longest: int) -> int:
    return int(np.ceil(np.log2(longest))) if longest > 1 else 0


@pytest.mark.parametrize("items", [None, 4])
@pytest.mark.parametrize(
    "runs",
    [[1] * 7, [2] * 5, [8, 8, 1], [9, 1, 9], [27, 3, 27, 1, 14], [5, 27, 1, 2, 16, 17, 1]],
)
def test_segment_heads_sums_each_run_into_its_first_position(runs, items):
    rng = np.random.default_rng(len(runs) * 31 + sum(runs))
    n_pad = 11
    seg = np.repeat(np.arange(len(runs) + 1), runs + [n_pad])  # padding slot last
    shape = (len(seg),) if items is None else (items, len(seg))
    vals = rng.standard_normal(shape, dtype=np.float32)
    vals[..., -n_pad:] = 0  # padding products multiply the zero slots
    got = np.asarray(_segment_heads(vals, seg, passes_for(max(runs))))
    want = np.zeros(vals.shape[:-1] + (len(runs) + 1,), np.float32)
    np.add.at(want.T, seg, vals.T)
    heads = np.cumsum(runs) - runs
    np.testing.assert_allclose(got[..., heads], want[..., :-1], rtol=TOL, atol=TOL)


def test_segment_heads_leaves_all_padding_and_zero_passes_alone():
    seg = np.full(40, 3)
    vals = np.zeros((2, 40), np.float32)
    assert not np.asarray(_segment_heads(vals, seg, 5)).any()
    ones = np.arange(6, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(_segment_heads(ones, np.arange(6), 0)), ones)


@pytest.mark.parametrize("n", [6, 12])
def test_stencil_plans_take_five_passes_and_fold_by_identity(n):
    inst, _ = amg_instances(n)
    plan = repro.plan(inst, p=1, model="fine").execution_plan
    assert plan.segment_passes == 5
    heads = plan.prod_heads[0]
    runs = np.diff(heads, append=plan.stats["n_mult"])
    assert (runs.max(), runs.min(), (heads >= 0).all()) == (27, 1, True)
    # at p=1 every produced slot is the owned slot of the same rank
    assert np.array_equal(plan.compute["prod_to_owned"][0], np.arange(inst.c.nnz))


def test_one_product_a_slot_takes_no_pass():
    rng = np.random.default_rng(4)
    a_s, b_s = from_dense(np.eye(8)), random_structure(8, 9, 0.4, rng)
    plan = repro.plan(a_s, b_s, p=1, model="fine").execution_plan
    assert plan.segment_passes == 0
    assert np.array_equal(plan.prod_heads[0], np.arange(b_s.nnz))


def test_padding_runs_and_empty_devices_count_no_passes(amg6):
    """A device with three products sees a long padding run, which the
    passes do not count; a device with none has no run heads."""
    from repro.core.spgemm_models import SpGEMMInstance

    inst = SpGEMMInstance(*amg6)
    mult = np.zeros(inst.n_mult, np.int64)
    mult[:3] = 1
    plan = build_fine_plan(inst, mult, 3)
    assert plan.segment_passes == 5
    assert (plan.prod_heads[2] == -1).all()
    assert (plan.prod_heads[1] >= 0).sum() == len(np.unique(inst.mult_c_pos[:3]))


def test_unsorted_lists_are_refused(amg6):
    plan = repro.plan(*amg6, p=1, model="fine").execution_plan
    plan.compute["pair_c"] = plan.compute["pair_c"][:, ::-1].copy()
    with pytest.raises(ValueError, match="not sorted by produced slot"):
        plan.prod_heads


def scatter_step(plan, a_vals, b_vals) -> np.ndarray:
    """The p=1 fine step's local compute as it was before the segmented
    sum, in float32: every product scatter-added into the produced table,
    the produced table scatter-added into the owned C slots through
    ``prod_to_owned``.  C in canonical order, (nnz_c, r, c) for blocks."""
    (r, k), (_, c) = plan.blocks

    def table(vals, ids, n_slots, shape):
        tab = np.zeros((n_slots, *shape), np.float32)
        tab[: ids.shape[1]] = vals.reshape(-1, *shape)[ids[0]]
        return tab

    a_tab = table(a_vals, plan.local_ids["a_nz"], plan.a_table_slots, (r, k))
    b_tab = table(b_vals, plan.local_ids["b_nz"], plan.b_table_slots, (k, c))
    pa, pb, pc = (plan.compute[n][0] for n in ("pair_a", "pair_b", "pair_c"))
    prods = np.einsum("mik,mkj->mij", a_tab[pa], b_tab[pb])
    partial = np.zeros((plan.n_prod_slots, r, c), np.float32)
    np.add.at(partial, pc, prods)
    own = plan.compute["prod_to_owned"][0]
    c_slots = np.zeros((plan.n_c_slots, r, c), np.float32)
    np.add.at(c_slots, own[own >= 0], partial[:-1][own >= 0])
    local_c = plan.local_ids["c_nz"][0]
    out = np.zeros((len(plan.c_part), r, c), np.float32)
    out[local_c] = c_slots[: len(local_c)]
    return out if plan.blocks != SCALAR_BLOCKS else out.reshape(-1)


def expand(structure, vals, block) -> sp.csr_matrix:
    csr = structure.csr
    data = np.asarray(vals, np.float64).reshape(-1, *block)
    shape = (csr.shape[0] * block[0], csr.shape[1] * block[1])
    return sp.bsr_matrix((data, csr.indices, csr.indptr), shape=shape).tocsr()


@pytest.mark.parametrize("blocks", [SCALAR_BLOCKS, ELASTIC], ids=["scalar", "3x3.3x6"])
@pytest.mark.parametrize("model", ["fine", "monoA", "monoB"])
def test_one_device_step_matches_the_scatter_step_and_scipy(amg6, model, blocks):
    a_s, b_s = amg6
    (r, k), (_, c) = blocks
    handle = repro.plan(a_s, b_s, p=1, model=model, blocks=blocks)
    plan = handle.execution_plan
    rng = np.random.default_rng(17)
    a_vals = rng.standard_normal((a_s.nnz, r, k), dtype=np.float32)
    b_vals = rng.standard_normal((b_s.nnz, k, c), dtype=np.float32)
    if blocks == SCALAR_BLOCKS:
        a_vals, b_vals = a_vals.reshape(-1), b_vals.reshape(-1)
    exe = handle.compile()
    got = owned_c_values(exe.runtime(*exe.pack(a_vals, b_vals)), plan).reshape(-1, r, c)
    a64, b64 = expand(a_s, a_vals, (r, k)), expand(b_s, b_vals, (k, c))
    rows, cols = handle.instance.c.coo()

    def at_c(m):
        dense = m.toarray().reshape(len(a_s.indptr) - 1, r, -1, c)
        return dense[rows, :, cols, :]

    scale = at_c(abs(a64) @ abs(b64))
    old = scatter_step(plan, a_vals, b_vals).reshape(-1, r, c)
    assert np.abs(got - old).max() <= TOL * scale.max()
    assert (np.abs(got - at_c(a64 @ b64)) <= TOL * scale).all()


def scatters_by_scope(hlo: str) -> dict[str, int]:
    """Scatter instructions of every computation (fused ones too), counted
    by the program scope of their ``op_name``."""
    out: dict[str, int] = {}
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if not m or m["op"] != "scatter":
            continue
        md = re.search(r'op_name="([^"]*)"', line)
        for scope in set(SCOPE.findall(md.group(1))) if md else {""}:
            out[scope] = out.get(scope, 0) + 1
    return out


@pytest.mark.parametrize("blocks", [SCALAR_BLOCKS, ELASTIC], ids=["scalar", "3x3.3x6"])
def test_one_device_fine_program_has_no_scatter_under_local(amg6, blocks):
    exe = repro.plan(*amg6, p=1, model="fine", blocks=blocks).compile(dtype=np.float32)
    hlo = exe.runtime._compiled.as_text()
    scatters = scatters_by_scope(hlo)
    assert scatters.get("repro.scatter_values", 0) > 0  # the value scatter is read
    assert "repro.local" in hlo and "repro.local" not in scatters, scatters
