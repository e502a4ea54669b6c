"""Plan IR invariants: vectorized construction == loop-based reference,
routing-table consistency, and a host-side (numpy) simulation of the monoC
routes — no multi-device jax needed."""
import numpy as np
import pytest

from repro.core import SpGEMMInstance, build_model, evaluate
from repro.core.spgemm_models import _lin_lookup
from repro.distributed import build_rowwise_plan
from repro.distributed.plan import build_rowwise_plan_loop
from repro.distributed.plan_ir import padded_id_lists, plan_monoC_from_dense
from repro.distributed.registry import get_spec
from repro.kernels.bsr_spgemm import build_pair_lists, build_pair_lists_loop
from repro.sparse.structure import random_structure


def _instance(seed, i=40, k=32, j=36, density=0.15):
    rng = np.random.default_rng(seed)
    return SpGEMMInstance(
        random_structure(i, k, density, rng), random_structure(k, j, density, rng)
    )


# ---------------------------------------------------------------------------
# vectorized == loop (byte-identical routing tables)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(6))
def test_vectorized_rowwise_plan_matches_loop(seed):
    rng = np.random.default_rng(seed)
    inst = _instance(seed)
    p = int(rng.integers(2, 7))
    row_part = rng.integers(0, p, inst.shape[0])
    b_part = rng.integers(0, p, inst.shape[1]) if seed % 2 else None
    vec = build_rowwise_plan(inst, row_part, p, b_part)
    loop = build_rowwise_plan_loop(inst, row_part, p, b_part)
    assert np.array_equal(vec.send_idx, loop.send_idx)
    assert np.array_equal(vec.recv_key, loop.recv_key)
    assert np.array_equal(vec.local_rows, loop.local_rows)
    assert np.array_equal(vec.local_b_rows, loop.local_b_rows)
    assert vec.comm_words_ideal == loop.comm_words_ideal
    assert vec.comm_words_padded == loop.comm_words_padded
    assert vec.send_idx.dtype == np.int64


@pytest.mark.parametrize("seed", range(6))
def test_vectorized_pair_lists_match_loop(seed):
    rng = np.random.default_rng(seed)
    na, nb = int(rng.integers(0, 40)), int(rng.integers(0, 40))
    K, GR, GC = (int(rng.integers(1, 9)) for _ in range(3))
    args = (
        rng.integers(0, GR, na),
        rng.integers(0, K, na),
        rng.integers(0, K, nb),
        rng.integers(0, GC, nb),
    )
    for got, want in zip(build_pair_lists(*args), build_pair_lists_loop(*args)):
        assert np.array_equal(got, want)
        assert got.dtype == np.int64


# ---------------------------------------------------------------------------
# IR invariants
# ---------------------------------------------------------------------------
def test_padded_id_lists_roundtrip():
    rng = np.random.default_rng(0)
    p = 5
    part = rng.integers(0, p, 37)
    local_ids, local_of = padded_id_lists(part, p)
    for d in range(p):
        owned = local_ids[d][local_ids[d] >= 0]
        assert np.array_equal(owned, np.flatnonzero(part == d))
        assert np.array_equal(local_of[owned], np.arange(len(owned)))


def test_route_accounting_and_membership():
    inst = _instance(3)
    rng = np.random.default_rng(3)
    p = 4
    plan = build_rowwise_plan(inst, rng.integers(0, p, inst.shape[0]), p)
    route = plan.routes["expand"]
    assert route.items_padded >= route.items_ideal
    assert int((route.recv_key >= 0).sum()) == route.items_ideal
    # a device never ships to itself; padding is aligned between the tables
    for s in range(p):
        assert (route.recv_key[s, s] == -1).all()
    assert np.array_equal(route.send_idx >= 0, route.recv_key >= 0)
    # shipped local slots resolve to the advertised global row
    s_ids, d_ids, t_ids = np.nonzero(route.send_idx >= 0)
    local = route.send_idx[s_ids, d_ids, t_ids]
    assert np.array_equal(
        plan.local_ids["b_row"][s_ids, local], route.recv_key[s_ids, d_ids, t_ids]
    )


def test_outer_plan_fold_volume_via_stats():
    """Outer keeps A column k and B row k on k's part, so only C partials
    move (the fold), and their words are the outer hypergraph's
    connectivity."""
    inst = _instance(4)
    rng = np.random.default_rng(4)
    p = 4
    k_part = rng.integers(0, p, inst.shape[1])
    plan = get_spec("outer").lower(inst, k_part, p)
    moving = {name for name, r in plan.routes.items() if r.items_ideal}
    assert moving == {"reduce_c"}
    hg = build_model(inst, "outer")
    assert plan.comm_words_ideal == evaluate(hg, k_part, p).connectivity > 0
    assert plan.comm_words_padded >= plan.comm_words_ideal
    assert 0.0 <= plan.padding_fraction <= 1.0


def test_monoC_plan_host_simulation():
    """Simulate the two expand routes with numpy gathers and run the pair
    lists over the resulting slot tables: must reproduce dense A @ B."""
    rng = np.random.default_rng(5)
    I, K, J, block, p = 36, 28, 32, 4, 4
    a = rng.standard_normal((I, K)).astype(np.float32) * (rng.random((I, K)) < 0.2)
    b = rng.standard_normal((K, J)).astype(np.float32) * (rng.random((K, J)) < 0.2)
    plan, inst = plan_monoC_from_dense(a, b, block, p)
    from repro.sparse.bsr import to_bsr

    ab, bb = to_bsr(a, block, block), to_bsr(b, block, block)

    def tables(blocks, local_ids, route):
        N_max, T = local_ids.shape[1], route.T
        tabs = np.zeros((p, N_max + p * T + 1, block, block), np.float32)
        dev, slot = np.nonzero(local_ids >= 0)
        tabs[dev, slot] = blocks[local_ids[dev, slot]]
        s_ids, d_ids, t_ids = np.nonzero(route.recv_key >= 0)
        tabs[d_ids, N_max + s_ids * T + t_ids] = blocks[
            route.recv_key[s_ids, d_ids, t_ids]
        ]
        return tabs

    a_tabs = tables(ab.blocks, plan.local_ids["a_nz"], plan.routes["expand_a"])
    b_tabs = tables(bb.blocks, plan.local_ids["b_nz"], plan.routes["expand_b"])
    pa, pb, pc = (plan.compute[k] for k in ("pair_a", "pair_b", "pair_c"))
    c_slots = np.zeros((p, plan.n_c_slots, block, block), np.float32)
    for d in range(p):
        np.add.at(
            c_slots[d], pc[d], np.einsum("nij,njk->nik", a_tabs[d][pa[d]], b_tabs[d][pb[d]])
        )
    # scatter back to dense
    gr, gc = inst.c.shape
    crow, ccol = inst.c.coo()
    out = np.zeros((gr, gc, block, block), np.float32)
    dev, slot = np.nonzero(plan.local_ids["c_nz"] >= 0)
    gids = plan.local_ids["c_nz"][dev, slot]
    out[crow[gids], ccol[gids]] = c_slots[dev, slot]
    dense = out.transpose(0, 2, 1, 3).reshape(gr * block, gc * block)[:I, :J]
    np.testing.assert_allclose(dense, a @ b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# satellite regression: _lin_lookup out-of-range queries
# ---------------------------------------------------------------------------
def test_lin_lookup_out_of_range_raises_keyerror():
    from repro.sparse.structure import from_coo

    s = from_coo([0, 1], [0, 1], (2, 2))
    # absent but within range: plain membership failure
    with pytest.raises(KeyError):
        _lin_lookup(s, np.array([1]), np.array([0]))
    # past the last stored linear index: searchsorted returns len(lin_sorted)
    # and used to IndexError on the gather before the intended KeyError
    s2 = from_coo([0], [0], (2, 2))
    with pytest.raises(KeyError):
        _lin_lookup(s2, np.array([1]), np.array([1]))
    # in-range queries still resolve
    assert np.array_equal(
        _lin_lookup(s, np.array([0, 1]), np.array([0, 1])), np.array([0, 1])
    )
