"""Distributed SpGEMM executors (shard_map) + inspector-executor planning.

The supported public surface is listed in ``__all__``; the declarative
``ModelSpec`` registry (``repro.distributed.registry``) is the single
source for which models lower to executors and how.  Attributes resolve
lazily (PEP 562) so importing the planning-side modules (``registry``,
``plan_ir``, ``select`` — pure numpy/scipy) never drags jax in; only
touching an executor or the runtime does.

The loop-based reference builder ``build_rowwise_plan_loop`` is
deliberately *not* part of the public surface anymore — it remains
importable from ``repro.distributed.plan`` for the byte-identical pin
test, and accessing it through this package emits a one-time
DeprecationWarning.
"""
from __future__ import annotations

import importlib

__all__ = [
    "CompiledSpGEMM",
    "SpGEMMSession",
    "compile_spgemm",
    "ExecutionPlan",
    "Route",
    "RowwisePlan",
    "MonoCPlan",
    "FinePlan",
    "SummaPlan",
    "ModelSpec",
    "MODEL_SPECS",
    "executable_models",
    "get_spec",
    "build_rowwise_plan",
    "build_monoC_plan",
    "build_fine_plan",
    "build_volume_plan",
    "derive_owner_from_pins",
    "measured_route_words",
    "plan_fine_from_dense",
    "plan_monoC_from_dense",
    "build_summa_plan",
    "summa_words_ideal",
    "rowwise_spgemm",
    "monoC_spgemm",
    "fine_spgemm",
]

_HOME = {
    "repro.distributed.plan_ir": (
        "ExecutionPlan",
        "FinePlan",
        "MonoCPlan",
        "Route",
        "RowwisePlan",
        "build_fine_plan",
        "build_monoC_plan",
        "build_rowwise_plan",
        "build_volume_plan",
        "derive_owner_from_pins",
        "measured_route_words",
        "plan_fine_from_dense",
        "plan_monoC_from_dense",
    ),
    "repro.distributed.registry": (
        "MODEL_SPECS",
        "ModelSpec",
        "executable_models",
        "get_spec",
    ),
    "repro.distributed.runtime": ("CompiledSpGEMM", "compile_spgemm"),
    "repro.distributed.summa": (
        "SummaPlan",
        "build_summa_plan",
        "summa_words_ideal",
    ),
    "repro.distributed.session": ("SpGEMMSession",),
    "repro.distributed.spgemm_exec": (
        "fine_spgemm",
        "monoC_spgemm",
        "rowwise_spgemm",
    ),
}
_EXPORT_TO_MODULE = {name: mod for mod, names in _HOME.items() for name in names}
assert set(_EXPORT_TO_MODULE) == set(__all__), "lazy export table out of sync"

_DEPRECATION_WARNED = False


def __getattr__(name: str):
    # deprecation shim (warn once): the loop reference left the public
    # surface in the api_redesign PR but old call sites keep working
    if name == "build_rowwise_plan_loop":
        global _DEPRECATION_WARNED
        if not _DEPRECATION_WARNED:
            import warnings

            warnings.warn(
                "repro.distributed.build_rowwise_plan_loop is deprecated; "
                "import it from repro.distributed.plan (it is a loop-based "
                "reference implementation, not a supported entry point)",
                DeprecationWarning,
                stacklevel=2,
            )
            _DEPRECATION_WARNED = True
        from repro.distributed.plan import build_rowwise_plan_loop

        return build_rowwise_plan_loop
    module = _EXPORT_TO_MODULE.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value  # cache: later lookups skip __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
