"""Public-surface snapshot: the documented front door cannot rot silently.

Pins ``repro.__all__``, the signature of ``repro.plan``, the demotion of the
loop-reference builder from ``repro.distributed.__all__`` (with its
deprecation shim), and the lazy-import property (``import repro`` must not
drag jax in — planning is a numpy/scipy affair).
"""
import inspect
import subprocess
import sys
import warnings

import pytest

import repro


def test_top_level_all_is_pinned():
    assert repro.__all__ == [
        "MODELS",
        "MODEL_SPECS",
        "CompiledSpGEMM",
        "FaultPolicy",
        "ModelSpec",
        "PlannedSpGEMM",
        "SpGEMMInstance",
        "SpGEMMSession",
        "device_count",
        "executable_models",
        "plan",
        "session",
    ]


def test_every_exported_name_resolves():
    for name in repro.__all__:
        assert getattr(repro, name) is not None, name
    assert set(repro.__all__) <= set(dir(repro))


def test_plan_signature_is_pinned():
    sig = inspect.signature(repro.plan)
    assert list(sig.parameters) == [
        "A", "B", "p", "model", "eps", "seed", "name", "include_nz", "engine",
        "coarsen", "blocks",
    ]
    defaults = {
        k: v.default
        for k, v in sig.parameters.items()
        if v.default is not inspect.Parameter.empty
    }
    assert defaults == {
        "B": None,
        "p": 8,
        "model": "auto",
        "eps": 0.10,
        "seed": 0,
        "name": "",
        "include_nz": False,
        "engine": "flat",
        "coarsen": "auto",
        "blocks": None,
    }


def test_session_signature_is_pinned():
    sig = inspect.signature(repro.session)
    assert list(sig.parameters) == [
        "p", "model", "eps", "seed", "engine", "store_dir", "policy", "kwargs",
    ]
    defaults = {
        k: v.default
        for k, v in sig.parameters.items()
        if v.default is not inspect.Parameter.empty
    }
    assert defaults == {
        "p": 8,
        "model": "auto",
        "eps": 0.10,
        "seed": 0,
        "engine": "flat",
        "store_dir": None,
        "policy": None,
    }
    for attr in ("multiply", "stats", "__call__"):
        assert callable(getattr(repro.SpGEMMSession, attr)), attr


def test_planned_handle_surface_is_pinned():
    for attr in ("cost_report", "compile", "execute", "costs"):
        assert callable(getattr(repro.PlannedSpGEMM, attr)), attr
    assert repro.PlannedSpGEMM.__call__ is repro.PlannedSpGEMM.execute
    for attr in ("pack", "__call__"):
        assert callable(getattr(repro.CompiledSpGEMM, attr)), attr


def test_registry_is_the_executable_source_of_truth():
    # the seven paper models plus the oblivious SUMMA baseline (by name only;
    # never part of model="auto")
    assert tuple(repro.MODEL_SPECS) == (*repro.MODELS, "summa2d")
    assert repro.executable_models() == repro.MODELS
    assert repro.executable_models() == (
        "fine", "rowwise", "columnwise", "outer", "monoA", "monoB", "monoC"
    )


def test_planning_side_imports_do_not_import_jax():
    """The front door resolves lazily: planning (model build, partitioning,
    plan lowering, selection, cost reports) is a pure numpy/scipy affair —
    only compiling/executing touches jax."""
    code = (
        "import sys; import repro, repro.api, repro.core, repro.sparse; "
        "import repro.distributed.registry, repro.distributed.select, "
        "repro.distributed.plan_ir, repro.distributed.session; "
        "import repro.resilience, repro.testing, repro.checkpoint; "
        "import repro.launch.serve; "
        "sys.exit(1 if 'jax' in sys.modules else 0)"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert out.returncode == 0, out.stderr.decode()


def test_loop_reference_demoted_but_shimmed():
    import repro.distributed as dist
    from repro.distributed import plan as plan_mod

    assert "build_rowwise_plan_loop" not in dist.__all__
    # the shim returns the real function (and warns at least once per process)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        assert dist.build_rowwise_plan_loop is plan_mod.build_rowwise_plan_loop


def test_distributed_exposes_no_deleted_name():
    """Columnwise and outer run on the fine executor: outer's plan class,
    builder and dense wrapper and the superseded SpSUMMA are gone from the
    package, with no shim left behind."""
    import repro.distributed as dist

    for name in ("OuterPlan", "build_outer_plan", "outer_product_spgemm", "spsumma"):
        assert name not in dist.__all__, name
        with pytest.raises(AttributeError):
            getattr(dist, name)


def test_distributed_all_lists_only_supported_entry_points():
    import repro.distributed as dist

    for name in dist.__all__:
        assert not name.endswith("_loop"), name
        assert getattr(dist, name) is not None, name


def test_unknown_model_raises():
    import numpy as np

    with pytest.raises(ValueError, match="unknown model"):
        repro.plan(np.eye(4), np.eye(4), p=2, model="rowwize")
