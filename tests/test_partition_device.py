"""Device-engine invariants: ``partition(engine="device")`` vs the flat host
engine (cross-engine agreement, satellite of the device-engine PR).

The device engine is an *above-threshold* engine: the driver routes
instances at or below ``DEVICE_MIN_VERTICES`` to the host quality path, so
these tests monkeypatch the threshold to 0 to exercise the jax kernel on the
small ``test_partition_invariants.py`` instance family.  Sampled label
propagation from random starts is weaker than full multilevel recursive
bisection at these sizes (that is exactly why the threshold exists), so the
quality gate is a *bounded* connectivity ratio rather than parity; balance,
determinism, the size-threshold deferral, the jax-absent fallback and the
compile-once retrace accounting are exact.
"""
import importlib
import sys
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import SpGEMMInstance, build_model, evaluate, partition
from repro.sparse.structure import random_structure

partition_mod = importlib.import_module("repro.core.partition")
refine_device = importlib.import_module("repro.core.refine_device")


def _instance(seed=0, shape=(60, 50, 55), density=0.08):
    rng = np.random.default_rng(seed)
    a = random_structure(shape[0], shape[1], density, rng)
    b = random_structure(shape[1], shape[2], density, rng)
    return SpGEMMInstance(a, b)


@pytest.fixture(autouse=True)
def fresh_fallback_warnings(monkeypatch):
    """The device->flat fallback warns once per process per reason; give each
    test its own warned-set so warning assertions stay order-independent."""
    monkeypatch.setattr(partition_mod, "_FALLBACK_WARNED", set())


@pytest.fixture
def device_everywhere(monkeypatch):
    """Route every size through the device engine."""
    monkeypatch.setattr(partition_mod, "DEVICE_MIN_VERTICES", 0)


# ---------------------------------------------------------------------------
# balance + determinism
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("p,eps", [(2, 0.05), (4, 0.10), (8, 0.10)])
def test_device_balance_cap_respected(device_everywhere, p, eps):
    hg = build_model(_instance(1, shape=(90, 70, 80)), "rowwise")
    res = partition(hg, p, eps=eps, seed=0, engine="device")
    w = hg.w_comp.astype(np.float64)
    part_w = np.bincount(res.parts, weights=w, minlength=p)
    cap = max((1 + eps) * w.sum() / p, float(w.max()))
    assert (part_w <= cap + 1e-9).all()


def test_device_reported_connectivity_matches_fresh_evaluation(device_everywhere):
    hg = build_model(_instance(2), "rowwise")
    res = partition(hg, 4, eps=0.10, seed=3, engine="device")
    assert res.connectivity == evaluate(hg, res.parts, 4).connectivity


def test_device_deterministic_for_fixed_seed(device_everywhere):
    hg = build_model(_instance(3, shape=(80, 60, 70)), "rowwise")
    a = partition(hg, 4, eps=0.10, seed=7, engine="device")
    b = partition(hg, 4, eps=0.10, seed=7, engine="device")
    assert np.array_equal(a.parts, b.parts)
    assert a.connectivity == b.connectivity
    c = partition(hg, 4, eps=0.10, seed=8, engine="device")
    # different seed is allowed to (and generally does) differ
    assert c.parts.shape == a.parts.shape


# ---------------------------------------------------------------------------
# bounded connectivity ratio vs the flat engine
# ---------------------------------------------------------------------------
def test_device_connectivity_ratio_bounded_vs_flat(device_everywhere):
    """Per-cell and aggregate bounds over the invariant-suite instance grid
    (all p in {2, 4, 8}).  Empirically the device engine lands ~1.10x flat in
    aggregate at these sub-threshold sizes (worst cell ~1.35); the asserted
    bounds leave headroom for sampling noise, not for regressions."""
    tot_dev = tot_flat = 0
    for seed in (0, 4, 5):
        inst = _instance(seed, shape=(60 + 10 * seed, 50 + 5 * seed, 55))
        for model in ("rowwise", "fine"):
            hg = build_model(inst, model)
            for p in (2, 4, 8):
                cd = partition(hg, p, eps=0.10, seed=seed, engine="device").connectivity
                cf = partition(hg, p, eps=0.10, seed=seed, engine="flat").connectivity
                assert cd <= 1.6 * cf, f"{model}/p{p}/seed{seed}: {cd} vs {cf}"
                tot_dev += cd
                tot_flat += cf
    assert tot_dev <= 1.25 * tot_flat


# ---------------------------------------------------------------------------
# driver routing: threshold deferral + jax-absent fallback
# ---------------------------------------------------------------------------
def test_device_defers_to_host_below_threshold():
    """Without the monkeypatch, sub-threshold instances take the flat
    quality path bit-for-bit (host FM stays authoritative there)."""
    hg = build_model(_instance(0), "rowwise")
    assert hg.n_vertices <= partition_mod.DEVICE_MIN_VERTICES
    a = partition(hg, 4, eps=0.10, seed=0, engine="device")
    b = partition(hg, 4, eps=0.10, seed=0, engine="flat")
    assert np.array_equal(a.parts, b.parts)
    assert a.connectivity == b.connectivity


def test_device_falls_back_to_flat_without_jax(device_everywhere, monkeypatch):
    """With the refine_device import blocked (as when jax is absent), the
    driver warns ONCE and produces exactly the flat-engine result —
    planning-side callers keep working with no jax installed (PR 5's
    contract), and a replanning loop doesn't spam a warning per call."""
    monkeypatch.setitem(sys.modules, "repro.core.refine_device", None)
    hg = build_model(_instance(1), "rowwise")
    with pytest.warns(RuntimeWarning, match="falling back"):
        a = partition(hg, 4, eps=0.10, seed=0, engine="device")
    b = partition(hg, 4, eps=0.10, seed=0, engine="flat")
    assert np.array_equal(a.parts, b.parts)
    # second call: same fallback, no second warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        c = partition(hg, 4, eps=0.10, seed=0, engine="device")
    assert np.array_equal(c.parts, b.parts)


def test_device_engine_failure_falls_back_to_flat(device_everywhere, monkeypatch):
    """A device engine that fails at run time (OOM, kernel error) raises —
    no silent switch to the flat engine.  Resilience lives in the session's
    FaultPolicy, which records an ``engine_fallback`` event when it walks
    the engine chain."""

    def boom(hg, p, part_cap, seed, rd, coarsen):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected device OOM")

    monkeypatch.setattr(partition_mod, "_partition_device", boom)
    hg = build_model(_instance(1), "rowwise")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="injected device OOM"):
            partition(hg, 4, eps=0.10, seed=0, engine="device")


def test_unknown_engine_still_rejected():
    hg = build_model(_instance(0), "rowwise")
    with pytest.raises(ValueError):
        partition(hg, 2, engine="device2")


# ---------------------------------------------------------------------------
# compile-once shape bucketing
# ---------------------------------------------------------------------------
def test_device_kernel_retraces_once_per_shape_bucket(device_everywhere):
    """Repeat calls — and different seeds — on same-bucket shapes must reuse
    the jitted refiner: the retrace counter moves only on the first call."""
    hg = build_model(_instance(4, shape=(80, 60, 70)), "rowwise")
    partition(hg, 4, eps=0.10, seed=0, engine="device")  # warm the cache
    before = refine_device.trace_count()
    partition(hg, 4, eps=0.10, seed=0, engine="device")
    partition(hg, 4, eps=0.10, seed=9, engine="device")
    assert refine_device.trace_count() == before
    # a different p is a different kernel: exactly one fresh trace per level
    partition(hg, 5, eps=0.10, seed=0, engine="device")
    after_p5 = refine_device.trace_count()
    assert after_p5 > before
    partition(hg, 5, eps=0.10, seed=1, engine="device")
    assert refine_device.trace_count() == after_p5


def test_refine_batch_is_balance_feasible_and_scored(device_everywhere):
    """Direct kernel contract: scores are finite, the argmin seed is the
    best, and feasible seeds respect the cap the kernel was given."""
    hg = build_model(_instance(5, shape=(90, 70, 80)), "fine")
    p = 4
    w = hg.w_comp.astype(np.float64)
    cap = max(1.25 * w.sum() / p, float(w.max()))
    batch0 = refine_device.initial_partitions(hg, p, seed=0)
    batch, scores = refine_device.refine_batch(hg, batch0, p, cap, rounds=8)
    assert batch.shape == batch0.shape
    assert ((batch >= 0) & (batch < p)).all()
    assert np.isfinite(scores).all()
    feasible = scores < 1e11  # below the infeasibility penalty
    assert feasible.any()
    for s in np.flatnonzero(feasible):
        pw = np.bincount(batch[s], weights=w, minlength=p)
        assert (pw <= cap + 1e-6).all()
