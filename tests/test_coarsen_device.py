"""Device-resident coarsening invariants (``core/coarsen_device.py`` and the
``engine="device", coarsen="auto"`` driver path).

The resident V-cycle replaces the host scipy descend with jitted cluster +
contract kernels; these tests pin the contracts that keep it honest:

- the cluster map is a valid contraction (every vertex lands in a real
  cluster, weights are conserved exactly, no cluster outgrows the cap the
  kernel was given),
- the end-to-end resident partition stays within a bounded connectivity
  ratio of the host-coarsening path it replaced,
- fixed seeds reproduce bit-identical partitions,
- repeated same-shape partitions never retrace a kernel (compile-once
  bucketing, the PR's perf contract), and
- a blocked ``coarsen_device`` import degrades to host coarsening with one
  warning and the identical host-coarsening result.

Like ``test_partition_device.py``, the device engine's size threshold is
monkeypatched to 0 so the small instances here exercise the kernels.
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
coarsen_device = importlib.import_module("repro.core.coarsen_device")


def _instance(seed=0, rows=900, inner=700, cols=800, density=0.01):
    rng = np.random.default_rng(seed)
    a = random_structure(rows, inner, density, rng)
    b = random_structure(inner, cols, density, rng)
    return SpGEMMInstance(a, b)


@pytest.fixture(autouse=True)
def fresh_fallback_warnings(monkeypatch):
    """The device fallback warns once per process per reason; give each test
    its own warned-set so warning assertions stay order-independent."""
    monkeypatch.setattr(partition_mod, "_FALLBACK_WARNED", set())


@pytest.fixture
def device_everywhere(monkeypatch):
    """Route every size through the device engine."""
    monkeypatch.setattr(partition_mod, "DEVICE_MIN_VERTICES", 0)


# ---------------------------------------------------------------------------
# cluster-map validity
# ---------------------------------------------------------------------------
def test_cluster_map_is_valid_capped_contraction():
    """One ``coarsen_level`` call yields a genuine contraction: every real
    vertex maps into [0, n_coarse), coarse weights are the exact per-cluster
    sums of fine weights, and no cluster exceeds the weight cap handed to
    the kernel."""
    hg = build_model(_instance(0), "rowwise")
    level = coarsen_device.finest_level(hg)
    w = hg.w_comp.astype(np.float64)
    cap = max(float(w.sum()) / 12.0, float(w.max()))
    out = coarsen_device.coarsen_level(level, cap, seed=0, index=0)
    assert out is not None, "clustering stalled on a healthy instance"
    coarse, cmap, n_coarse = out
    assert coarse.n_vertices == n_coarse
    assert 0 < n_coarse < hg.n_vertices
    cm = np.asarray(cmap)[: hg.n_vertices]
    assert cm.min() >= 0 and cm.max() < n_coarse
    coarse_w = np.asarray(coarse.args[3])[:n_coarse].astype(np.float64)
    summed = np.bincount(cm, weights=w, minlength=n_coarse)
    np.testing.assert_allclose(coarse_w, summed, rtol=1e-5)
    assert (coarse_w <= cap * (1 + 1e-6)).all()


def test_coarsen_level_preserves_total_weight_down_the_hierarchy():
    hg = build_model(_instance(1), "rowwise")
    total = float(hg.w_comp.sum())
    cap = max(total / 10.0, float(hg.w_comp.max()))
    level = coarsen_device.finest_level(hg)
    for index in range(3):
        out = coarsen_device.coarsen_level(level, cap, seed=0, index=index)
        if out is None:
            break
        level = out[0]
        lw = np.asarray(level.args[3])[: level.n_vertices]
        assert np.isclose(float(lw.sum()), total, rtol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end quality, balance and determinism
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("inst_seed", [3, 4])
def test_resident_connectivity_ratio_bounded_vs_host_coarsening(
    device_everywhere, inst_seed
):
    """The device descend may not give back more than 5% connectivity vs
    the host-coarsening device path it replaces (the bench gates the same
    bound at scale on er10k/p16)."""
    hg = build_model(_instance(inst_seed), "rowwise")
    dev = partition(hg, 4, eps=0.10, seed=0, engine="device")
    host = partition(hg, 4, eps=0.10, seed=0, engine="device", coarsen="host")
    assert dev.connectivity <= 1.05 * host.connectivity


def test_resident_balance_cap_respected(device_everywhere):
    p, eps = 4, 0.10
    hg = build_model(_instance(3), "rowwise")
    res = partition(hg, p, eps=eps, seed=0, engine="device")
    w = hg.w_comp.astype(np.float64)
    part_w = np.bincount(res.parts, weights=w, minlength=p)
    cap = max((1 + eps) * w.sum() / p, float(w.max()))
    assert (part_w <= cap + 1e-9).all()


def test_resident_deterministic_for_fixed_seed(device_everywhere):
    hg = build_model(_instance(4), "rowwise")
    a = partition(hg, 4, eps=0.10, seed=5, engine="device")
    b = partition(hg, 4, eps=0.10, seed=5, engine="device")
    assert np.array_equal(a.parts, b.parts)
    assert a.connectivity == b.connectivity
    assert a.connectivity == evaluate(hg, a.parts, 4).connectivity


# ---------------------------------------------------------------------------
# compile-once shape bucketing
# ---------------------------------------------------------------------------
def test_coarsen_kernels_retrace_once_per_shape_bucket(device_everywhere):
    """Repeated resident partitions of the same instance reuse every jitted
    cluster/contract kernel (and every refiner): the retrace counters move
    only while warming."""
    hg = build_model(_instance(5), "rowwise")
    partition(hg, 4, eps=0.10, seed=0, engine="device")  # warm the caches
    before_cd = coarsen_device.trace_count()
    before_rd = refine_device.trace_count()
    partition(hg, 4, eps=0.10, seed=0, engine="device")
    partition(hg, 4, eps=0.10, seed=0, engine="device")
    assert coarsen_device.trace_count() == before_cd
    assert refine_device.trace_count() == before_rd


def test_cluster_kernel_shared_across_p(device_everywhere):
    """The clusterer is partition-count-independent: changing ``p`` compiles
    fresh refiners but reuses the descend kernels for the finest level."""
    hg = build_model(_instance(6), "rowwise")
    partition(hg, 4, eps=0.10, seed=0, engine="device")  # warm p=4
    n_clusterers = len(coarsen_device._CLUSTERERS)
    partition(hg, 5, eps=0.10, seed=0, engine="device")
    # p=5 may descend to a different depth (the stop target scales with p)
    # but the finest-level clusterer key is identical — no new entry for it
    keys = list(coarsen_device._CLUSTERERS)
    finest = coarsen_device.finest_level(hg)
    assert sum(
        1
        for k in keys
        if k[:3] == (finest.nb, finest.mb, finest.pb)
    ) == 1
    assert len(coarsen_device._CLUSTERERS) >= n_clusterers


# ---------------------------------------------------------------------------
# degradation: blocked import falls back to host coarsening
# ---------------------------------------------------------------------------
def test_blocked_coarsen_import_falls_back_to_host_coarsening(
    device_everywhere, monkeypatch
):
    """With ``coarsen_device`` unimportable the driver warns ONCE and
    produces exactly the host-coarsening result — and an explicit
    ``coarsen="host"`` request never warns at all."""
    hg = build_model(_instance(7), "rowwise")
    want = partition(hg, 4, eps=0.10, seed=0, engine="device", coarsen="host")
    monkeypatch.setitem(sys.modules, "repro.core.coarsen_device", None)
    with pytest.warns(RuntimeWarning, match="host coarsening"):
        got = partition(hg, 4, eps=0.10, seed=0, engine="device")
    assert np.array_equal(got.parts, want.parts)
    assert got.connectivity == want.connectivity
    # second call: same fallback, no second warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = partition(hg, 4, eps=0.10, seed=0, engine="device")
    assert np.array_equal(again.parts, want.parts)


def test_runtime_coarsen_failure_falls_back_to_host_coarsening(
    device_everywhere, monkeypatch
):
    """A descend that dies at run time raises — no silent switch to host
    coarsening, and no warning stands in for the error."""

    def boom(level, cap, seed, index):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected device OOM")

    hg = build_model(_instance(8), "rowwise")
    monkeypatch.setattr(coarsen_device, "coarsen_level", boom)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="injected device OOM"):
            partition(hg, 4, eps=0.10, seed=0, engine="device")


def test_bad_coarsen_value_rejected():
    hg = build_model(_instance(0, rows=60, inner=50, cols=55, density=0.08),
                     "rowwise")
    with pytest.raises(ValueError):
        partition(hg, 2, engine="device", coarsen="gpu")
