"""The declarative ModelSpec registry: completeness, consistency with the
core model list, and the repro.api round-trip oracle.

Completeness is the load-bearing property: every name in ``MODELS`` must
carry a *full* executable spec (lowerer + runner + unpacker + mesh) — a
half-wired entry (e.g. a lowerer without an executor) is exactly the kind
of drift the old three-site dispatch allowed, and is an error here.

The p=1 oracle runs in-process (a 1-device mesh exercises the full packed
program); p in {4, 8} goes through the subprocess runner so forced host
devices never leak into this pytest process' jax.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.spgemm_models import MODELS, SpGEMMInstance
from repro.distributed.registry import (
    MODEL_SPECS,
    _fine_runner,
    _monoC_runner,
    _rowwise_runner,
    _summa_runner,
    executable_models,
    get_spec,
)
from repro.sparse.structure import random_structure

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


# ---------------------------------------------------------------------------
# completeness / consistency
# ---------------------------------------------------------------------------
def test_registry_covers_every_model_and_the_summa_baseline():
    assert set(MODELS) <= set(MODEL_SPECS)
    assert set(MODEL_SPECS) - set(MODELS) == {"summa2d"}
    # the oblivious baseline is executable but never enters model="auto"
    summa = MODEL_SPECS["summa2d"]
    assert summa.executable and not summa.in_auto and summa.build is None
    assert all(MODEL_SPECS[m].in_auto for m in MODELS)


@pytest.mark.parametrize("model", MODELS)
def test_every_model_fully_executable(model):
    """No half-wired and no volume-only entries remain: every paper model
    carries lowerer, runner, unpacker and mesh geometry as a package."""
    spec = get_spec(model)
    assert spec.name == model
    assert spec.family in ("1D", "2D", "3D")
    assert callable(spec.build)
    assert spec.executable, f"{model}: silently volume-only"
    parts = (spec.lower, spec.make_runner, spec.unpack)
    assert all(callable(f) for f in parts), f"{model}: partial spec"
    assert callable(spec.mesh_shape) and spec.axis_names
    assert spec.measured in ("exact", "useful")


def test_fine_runner_runs_every_model_but_rowwise_monoC_and_summa2d():
    """Columnwise, outer, monoA and monoB are fine plans, so four runners
    serve all eight entries."""
    runners = {name: spec.make_runner for name, spec in MODEL_SPECS.items()}
    others = {n for n, r in runners.items() if r is not _fine_runner}
    assert others == {"rowwise", "monoC", "summa2d"}
    assert runners["rowwise"] is _rowwise_runner
    assert runners["monoC"] is _monoC_runner and runners["summa2d"] is _summa_runner
    assert len(set(runners.values())) == 4


def test_executable_models_matches_select_surface():
    from repro.distributed.select import EXECUTABLE

    assert executable_models() == EXECUTABLE
    assert executable_models() == MODELS  # all seven, in MODELS order


def test_mesh_shapes_multiply_to_p():
    for p in (1, 2, 3, 4, 8):
        for model in (*MODELS, "summa2d"):
            spec = get_spec(model)
            if not spec.executable:
                continue
            shape = spec.mesh_shape(p)
            assert len(shape) == len(spec.axis_names), (model, p)
            assert int(np.prod(shape)) == p, (model, p, shape)


def test_get_spec_rejects_unknown_model():
    with pytest.raises(ValueError, match="unknown model"):
        get_spec("colwise")


# ---------------------------------------------------------------------------
# api round-trip oracle
# ---------------------------------------------------------------------------
def _valued(struct, rng):
    dense = np.zeros(struct.shape, dtype=np.float32)
    r, c = struct.coo()
    dense[r, c] = rng.standard_normal(len(r)).astype(np.float32)
    return dense


@pytest.mark.parametrize("model", executable_models())
def test_api_round_trip_matches_oracle_p1(model):
    """repro.plan(...).compile()(a_vals, b_vals) == dense oracle, with 1-D
    canonical value vectors for EVERY model (no block/mesh special-casing)."""
    import repro

    rng = np.random.default_rng(3)
    a_s = random_structure(18, 15, 0.25, rng)
    b_s = random_structure(15, 17, 0.25, rng)
    a = _valued(a_s, rng)
    b = _valued(b_s, rng)
    handle = repro.plan(a_s, b_s, p=1, model=model)
    got = handle.compile()(a[a_s.coo()], b[b_s.coo()])
    np.testing.assert_allclose(got, a @ b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("devices", [4, 8])
def test_api_round_trip_matches_oracle_multidev(devices):
    """All executable models + model="auto" through the front door at
    p in {4, 8} (subprocess: forced host devices)."""
    assert f"OK api p={devices}" in _run("api", devices=devices)


def test_api_monoC_executes_at_odd_p():
    """The registry's (1, p) monoC mesh fallback replaces the old
    caller-side odd-p skip."""
    assert "OK api_odd_p p=3" in _run("api_odd_p", devices=4)


def test_plan_auto_selects_min_predicted_words():
    import repro

    rng = np.random.default_rng(5)
    a_s = random_structure(26, 22, 0.15, rng)
    b_s = random_structure(22, 24, 0.15, rng)
    handle = repro.plan(a_s, b_s, p=4, model="auto")
    assert handle.model in executable_models()
    assert handle.selection is not None
    assert {r["model"] for r in handle.selection} == set(executable_models())
    best = min(handle.selection, key=lambda r: r["predicted_words"])
    assert best["model"] == handle.model and best["selected"]


def test_cost_report_planned_equals_predicted_for_every_model():
    """The front door exposes the paper's predicted == planned identity:
    exact for replicated-free plans and via item weighting for rowwise."""
    import repro

    rng = np.random.default_rng(6)
    a_s = random_structure(24, 20, 0.18, rng)
    b_s = random_structure(20, 22, 0.18, rng)
    for model in MODELS:
        report = repro.plan(a_s, b_s, p=4, model=model).cost_report()
        assert report["planned_words"] == report["predicted_words"], report


def test_plan_accepts_instance_for_reuse():
    """One symbolic inspection, many plans: repro.plan(inst, ...) reuses the
    instance instead of re-deriving S_C and the multiplication space."""
    import repro

    rng = np.random.default_rng(8)
    inst = SpGEMMInstance(
        random_structure(16, 14, 0.25, rng), random_structure(14, 15, 0.25, rng)
    )
    handle = repro.plan(inst, p=2, model="fine")
    assert handle.instance is inst
    with pytest.raises(ValueError, match="B must be omitted"):
        repro.plan(inst, inst.b, p=2, model="fine")
    with pytest.raises(ValueError, match="B is required"):
        repro.plan(inst.a, p=2, model="fine")


def test_plan_include_nz_places_nonzero_vertices():
    """include_nz keeps V^nz: fine lowers such partitions (placements become
    ownership, words still == connectivity); models whose lowerers don't
    understand them stay cost/analysis-only instead of lowering garbage."""
    import repro
    from repro.core import evaluate

    rng = np.random.default_rng(9)
    inst = SpGEMMInstance(
        random_structure(18, 15, 0.2, rng), random_structure(15, 16, 0.2, rng)
    )
    fine = repro.plan(inst, p=3, model="fine", include_nz=True)
    n_nz = inst.a.nnz + inst.b.nnz + inst.c.nnz
    assert fine.hypergraph.n_vertices == inst.n_mult + n_nz
    assert fine.executable
    assert fine.execution_plan.comm_words_ideal == int(
        evaluate(fine.hypergraph, fine.partition.parts, 3).connectivity
    )
    rw = repro.plan(inst, p=3, model="rowwise", include_nz=True)
    assert not rw.executable  # lowerer does not accept include_nz partitions
    assert rw.cost_report()["planned_words"] == rw.cost_report()["predicted_words"]
    with pytest.raises(ValueError, match="include_nz"):
        rw.compile()
    # auto must pick something that can run: fine is the only include_nz
    # lowerer, so it wins regardless of predicted words
    auto = repro.plan(inst, p=3, model="auto", include_nz=True)
    assert auto.model == "fine" and auto.executable


def test_planned_handle_has_identity_semantics():
    """ndarray-bearing fields: the handle must neither define value
    equality (ambiguous-truth ValueError territory) nor lose hashability —
    it is meant to key plan caches."""
    import dataclasses

    import repro

    rng = np.random.default_rng(10)
    inst = SpGEMMInstance(
        random_structure(12, 10, 0.3, rng), random_structure(10, 11, 0.3, rng)
    )
    h1 = repro.plan(inst, p=2, model="fine")
    h2 = dataclasses.replace(h1)
    assert h1 == h1 and h1 != h2  # identity, not field comparison
    assert len({h1, h2}) == 2  # hashable


def test_summa_baseline_is_planned_but_never_auto_selected():
    """The oblivious competitor is always available by name, carries an
    analytic (hypergraph-free) cost report whose planned == predicted, and
    never appears in the model="auto" contest."""
    import repro

    rng = np.random.default_rng(7)
    a_s = random_structure(14, 12, 0.25, rng)
    b_s = random_structure(12, 13, 0.25, rng)
    handle = repro.plan(a_s, b_s, p=2, model="summa2d")
    assert handle.hypergraph is None and handle.partition is None
    assert handle.p == 2  # falls back to the execution plan's p
    report = handle.cost_report()
    assert report["planned_words"] == report["predicted_words"]
    assert report["planned_messages"] >= 0 and "padded_words" in report
    with pytest.raises(ValueError, match="partition-free"):
        handle.costs()
    auto = repro.plan(a_s, b_s, p=2, model="auto")
    assert "summa2d" not in {r["model"] for r in auto.selection}
