"""Predicted-vs-planned communication: the hypergraph connectivity metric
equals the plan IR's scheduled words, for every model (via the generic
volume plan) and at item granularity for the fine-grained executor plan —
plus a host-side numpy simulation of the full expand-expand-reduce schedule.
No multi-device jax needed (the executor itself is oracle-tested in
``test_distributed_exec.py``)."""
import numpy as np
import pytest

from repro.core import SpGEMMInstance, build_model, evaluate, partition
from repro.core.spgemm_models import MODELS
from repro.distributed import (
    build_fine_plan,
    build_volume_plan,
    derive_owner_from_pins,
)
from repro.distributed.select import (
    build_executable_plan,
    measured_route_words,
    sweep_instance,
)
from repro.sparse.structure import random_structure


def _instance(seed, i=36, k=30, j=33, density=0.15):
    rng = np.random.default_rng(seed)
    return SpGEMMInstance(
        random_structure(i, k, density, rng), random_structure(k, j, density, rng)
    )


# ---------------------------------------------------------------------------
# every model: volume plan == connectivity metric (independent code paths)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("seed", [0, 1])
def test_volume_plan_matches_connectivity_every_model(model, seed):
    """For any model hypergraph and a random partition, lowering the cut to
    routing tables (transfer enumeration) counts exactly the words the
    connectivity metric predicts (lambda counting)."""
    inst = _instance(seed)
    rng = np.random.default_rng(seed + 100)
    hg = build_model(inst, model)
    p = int(rng.integers(2, 6))
    parts = rng.integers(0, p, hg.n_vertices)
    plan = build_volume_plan(hg, parts, p)
    assert plan.comm_words_ideal == evaluate(hg, parts, p).connectivity


@pytest.mark.parametrize("model", MODELS)
def test_volume_plan_matches_connectivity_partitioned(model):
    """Same identity on an optimized (non-random) partition."""
    inst = _instance(7)
    hg = build_model(inst, model)
    res = partition(hg, 4, eps=0.2, seed=0)
    plan = build_volume_plan(hg, res.parts, 4)
    assert plan.comm_words_ideal == evaluate(hg, res.parts, 4).connectivity


# ---------------------------------------------------------------------------
# fine plan: item-granularity routes realize exactly the connectivity cost
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(4))
def test_fine_plan_words_equal_connectivity(seed):
    inst = _instance(seed)
    rng = np.random.default_rng(seed)
    p = int(rng.integers(2, 6))
    hg = build_model(inst, "fine")
    parts = rng.integers(0, p, hg.n_vertices)
    plan = build_fine_plan(inst, parts, p)
    assert plan.comm_words_ideal == evaluate(hg, parts, p).connectivity
    for route in plan.routes.values():
        assert route.items_padded >= route.items_ideal
        assert np.array_equal(route.send_idx >= 0, route.recv_key >= 0)


def test_fine_plan_include_nz_partition_becomes_ownership():
    """Partitioning the include_nz fine hypergraph places the nonzero
    vertices too; the plan adopts those placements and its words still equal
    the (include_nz) connectivity cost."""
    inst = _instance(5)
    p = 4
    hg = build_model(inst, "fine", include_nz=True)
    res = partition(hg, p, eps=0.2, seed=0)
    plan = build_fine_plan(inst, res.parts, p)
    M, nA = inst.n_mult, inst.a.nnz
    assert np.array_equal(plan.a_part, res.parts[M : M + nA])
    assert plan.comm_words_ideal == evaluate(hg, res.parts, p).connectivity


def test_derive_owner_from_pins_places_owner_on_a_pin():
    rng = np.random.default_rng(0)
    p, n_items = 5, 30
    item = rng.integers(0, n_items, 120)
    part = rng.integers(0, p, 120)
    owner = derive_owner_from_pins(item, part, n_items, p)
    touched = {i: set(part[item == i]) for i in range(n_items)}
    for i in range(n_items):
        if touched[i]:
            assert owner[i] in touched[i]
        else:
            assert owner[i] == i % p  # round-robin fallback, no traffic


def test_fine_plan_every_produced_slot_owned_or_shipped():
    """Conservation: each partial-C slot a device produces either folds
    locally (the device owns that C nonzero) or ships on the reduce route
    exactly once — nothing is dropped, nothing is double-counted."""
    inst = _instance(6)
    rng = np.random.default_rng(6)
    p = 4
    plan = build_fine_plan(inst, rng.integers(0, p, inst.n_mult), p)
    prod_ids = plan.local_ids["c_prod"]
    prod_owned = plan.compute["prod_to_owned"]
    route = plan.routes["reduce_c"]
    shipped = np.zeros_like(prod_ids)
    s_ids, d_ids, t_ids = np.nonzero(route.send_idx >= 0)
    np.add.at(shipped, (s_ids, route.send_idx[s_ids, d_ids, t_ids]), 1)
    valid = prod_ids >= 0
    assert ((prod_owned >= 0).astype(int) + shipped)[valid].min() == 1
    assert ((prod_owned >= 0).astype(int) + shipped)[valid].max() == 1
    assert (shipped[~valid] == 0).all() and (prod_owned[~valid] == -1).all()
    # arriving items resolve to the destination's owned slot of that C id
    recv_slot = plan.compute["reduce_recv_slot"]
    keys = route.recv_key[s_ids, d_ids, t_ids]
    assert np.array_equal(
        plan.local_ids["c_nz"][d_ids, recv_slot[s_ids, d_ids, t_ids]], keys
    )


def test_fine_plan_host_simulation_reproduces_dense():
    """Simulate expand-expand-reduce with numpy gathers/segment-adds over
    the plan's tables: must reproduce dense A @ B."""
    rng = np.random.default_rng(8)
    inst = _instance(8, i=32, k=26, j=28, density=0.18)
    p = 4
    parts = rng.integers(0, p, inst.n_mult)
    plan = build_fine_plan(inst, parts, p)
    import scipy.sparse as sp

    I, K, J = inst.shape
    a = np.zeros((I, K))
    r, c = inst.a.coo()
    a[r, c] = rng.standard_normal(len(r))
    b = np.zeros((K, J))
    r, c = inst.b.coo()
    b[r, c] = rng.standard_normal(len(r))
    a_vals, b_vals = sp.csr_matrix(a).data, sp.csr_matrix(b).data

    def tables(vals, local_ids, route):
        N_max, T = local_ids.shape[1], route.T
        tabs = np.zeros((p, N_max + p * T + 1))
        dev, slot = np.nonzero(local_ids >= 0)
        tabs[dev, slot] = vals[local_ids[dev, slot]]
        s_ids, d_ids, t_ids = np.nonzero(route.recv_key >= 0)
        tabs[d_ids, N_max + s_ids * T + t_ids] = vals[route.recv_key[s_ids, d_ids, t_ids]]
        return tabs

    a_tabs = tables(a_vals, plan.local_ids["a_nz"], plan.routes["expand_a"])
    b_tabs = tables(b_vals, plan.local_ids["b_nz"], plan.routes["expand_b"])
    pa, pb, pc = (plan.compute[k] for k in ("pair_a", "pair_b", "pair_c"))
    R_max = plan.local_ids["c_prod"].shape[1]
    partial = np.zeros((p, R_max + 1))
    for d in range(p):
        np.add.at(partial[d], pc[d], a_tabs[d][pa[d]] * b_tabs[d][pb[d]])
    c_out = np.zeros((p, plan.n_c_slots))
    route = plan.routes["reduce_c"]
    recv_slot = plan.compute["reduce_recv_slot"]
    s_ids, d_ids, t_ids = np.nonzero(route.send_idx >= 0)
    np.add.at(
        c_out,
        (d_ids, recv_slot[s_ids, d_ids, t_ids]),
        partial[s_ids, route.send_idx[s_ids, d_ids, t_ids]],
    )
    prod_owned = plan.compute["prod_to_owned"]
    dev, slot = np.nonzero(prod_owned >= 0)
    np.add.at(c_out, (dev, prod_owned[dev, slot]), partial[dev, slot])
    out = np.zeros((I, J))
    crow, ccol = inst.c.coo()
    dev, slot = np.nonzero(plan.local_ids["c_nz"] >= 0)
    gids = plan.local_ids["c_nz"][dev, slot]
    out[crow[gids], ccol[gids]] = c_out[dev, slot]
    np.testing.assert_allclose(out, a @ b, rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# executable plans through the selection pipeline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("model", ["rowwise", "columnwise", "outer", "monoC", "fine"])
def test_executable_plan_measures_its_models_prediction(model):
    """Pin-derived ownership makes each executable plan's table-counted
    words equal the model's connectivity prediction."""
    inst = _instance(9)
    p = 4
    hg = build_model(inst, model)
    res = partition(hg, p, eps=0.2, seed=1)
    predicted = evaluate(hg, res.parts, p).connectivity
    plan = build_executable_plan(inst, model, res.parts, p)
    if model == "rowwise":
        measured = measured_route_words(plan, {"expand": inst.b.row_counts()})
    else:
        measured = measured_route_words(plan)
    assert measured == predicted


def test_sweep_instance_selects_min_predicted():
    inst = _instance(10)
    recs = sweep_instance(inst, p=4)
    ok = [r for r in recs if r["status"] == "ok"]
    assert {r["model"] for r in ok} == set(MODELS)
    best = min(ok, key=lambda r: r["predicted_words"])
    assert best["selected"] and sum(r["selected"] for r in recs) == 1
    for r in ok:
        assert r["volume_plan_words"] == r["predicted_words"]


def test_store_entry_of_a_deleted_plan_class_is_replanned_as_fine(tmp_path):
    """A store entry written when outer had its own plan class
    (``plan_class: "OuterPlan"``) is quarantined as an unknown class, and
    the session replans the structure as a fine-family plan."""
    import json
    import os

    import repro
    from repro.checkpoint import list_plans, restore_plan
    from repro.distributed.plan_ir import FinePlan
    from repro.resilience import FaultPolicy

    rng = np.random.default_rng(12)
    A = (rng.random((14, 12)) * (rng.random((14, 12)) < 0.35)).astype(np.float32)
    B = (rng.random((12, 13)) * (rng.random((12, 13)) < 0.35)).astype(np.float32)
    store = str(tmp_path / "store")
    policy = FaultPolicy(max_retries=2, backoff_s=0.0)
    repro.session(p=1, model="outer", policy=policy, store_dir=store).multiply(A, B)
    (key,) = list_plans(store)
    manifest_path = os.path.join(store, key, "manifest.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    manifest["plan_class"] = "OuterPlan"
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    s = repro.session(p=1, model="outer", policy=policy, store_dir=store)
    with pytest.warns(RuntimeWarning, match="quarantin"):
        C = np.asarray(s.multiply(A, B))
    np.testing.assert_allclose(C, A @ B, rtol=2e-4, atol=2e-4)
    assert [e.kind for e in s.events][:1] == ["cold_replan"]
    assert any(d.startswith(key + ".quarantined") for d in os.listdir(store))
    back = restore_plan(store, key)
    assert type(back.plan) is FinePlan and back.plan.model == "outer"
