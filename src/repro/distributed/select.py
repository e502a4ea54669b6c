"""Sparsity-dependent model selection: the model zoo as an algorithm picker.

The paper's seven hypergraph models are seven SpGEMM algorithms; which one
communicates least depends on the sparsity structure of the instance.  This
module closes the loop the models only predict:

1. ``sweep_instance`` partitions *every* model of an instance and records
   each one's predicted communication (the connectivity metric,
   ``comm.evaluate``);
2. for the models with executable plans it lowers the partition to an
   ``ExecutionPlan`` whose routing tables are built by an independent code
   path (transfer enumeration, ``plan_ir``), and counts the words those
   tables actually ship (``measured_route_words``);
3. when the process owns enough devices it runs the executors against the
   dense oracle, so "the words the cut prescribes" and "the words the
   program moves" are pinned to each other end to end.

For replicated-free plans — every model but rowwise, where each shipped
item is a single nonzero payload or one C partial — measured == predicted
exactly.  Row-wise ships whole dense rows, so its measured *useful* words
match the unit-cost prediction while its wire words exceed the
nnz-weighted cost; the sweep reports both so the gap is visible, as are
the padded all_to_all overhead and the message count
(``planned_messages``) for every model.

Everything model-specific (which models lower, how routed words are
weighted, what mesh/backend an executor wants) comes from the declarative
``registry.ModelSpec`` table — this module contains no per-model dispatch.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import partition
from repro.core.spgemm_models import MODELS, SpGEMMInstance
from repro.distributed.plan_ir import (  # noqa: F401  (re-export: tests use
    ExecutionPlan,                       # measured_route_words from here)
    build_volume_plan,
    measured_route_words,
)
from repro.distributed.registry import executable_models, get_spec

#: models whose partitions we can lower to an item-granularity executable
#: plan (derived from the registry — the old hand-maintained tuple is gone)
EXECUTABLE = executable_models()


def build_executable_plan(
    inst: SpGEMMInstance, model: str, parts: np.ndarray, p: int
) -> ExecutionPlan | None:
    """Lower a model partition to its executable plan, or None.

    Pure registry lookup: the per-model lowerers (with their pin-derived
    ownership — ``derive_owner_from_pins`` — so each cut net of
    connectivity lambda costs exactly lambda - 1 shipped items) live on the
    ``ModelSpec`` entries.
    """
    spec = get_spec(model)
    if spec.lower is None:
        return None
    return spec.lower(inst, np.asarray(parts, dtype=np.int64), p)


def _execute(handle, a_dense: np.ndarray, b_dense: np.ndarray, want: np.ndarray) -> dict:
    """Run a planned pipeline's executor on this process' devices and report
    its max error against the dense oracle ``want`` (computed once per
    instance by the caller).  Requires the process to own >= p devices (the
    multi-device CI job forces 8).

    Goes through the ``repro.api`` front door — mesh geometry, value
    packing, dtype promotion and backend defaults all come from the model's
    ``ModelSpec`` — with values taken straight off the instance structures
    (no dense -> sparse round trip).
    """
    inst = handle.instance
    ar, ac = inst.a.coo()
    br, bc = inst.b.coo()
    a_vals = a_dense[ar, ac]
    b_vals = b_dense[br, bc]
    exe = handle.compile(dtype=np.promote_types(a_vals.dtype, b_vals.dtype))
    got = exe(a_vals, b_vals)
    return {"exec_max_err": float(np.abs(got - want).max())}


def sweep_instance(
    inst: SpGEMMInstance,
    p: int,
    eps: float = 0.10,
    seed: int = 0,
    models: tuple[str, ...] = MODELS,
    a_dense: np.ndarray | None = None,
    b_dense: np.ndarray | None = None,
    execute: bool = False,
    pin_cap: int | None = None,
) -> list[dict]:
    """Partition every model, plan and (optionally) execute the executable
    ones, and report predicted vs planned vs measured words per model.

    Returns one record per model; the minimum ``predicted_words`` row is the
    selected algorithm for this instance.  ``execute`` additionally runs the
    executors when the process owns >= p devices (a no-op otherwise, so the
    sweep is safe in single-device harness runs).
    """
    from repro.api import PlannedSpGEMM, device_count

    records = []
    can_exec = False
    if execute and a_dense is not None:
        can_exec = device_count() >= p
    # the oracle matmul is only worth materializing when executors will run
    want = a_dense @ b_dense if can_exec else None
    for model in models:
        spec = get_spec(model)
        t0 = time.time()
        hg = spec.build(inst)
        if pin_cap is not None and hg.n_pins > pin_cap:
            records.append(
                {
                    "name": f"{inst.name}/select/{model}/p{p}",
                    "model": model,
                    "status": "skipped",
                    "reason": f"pins {hg.n_pins} > cap {pin_cap}",
                }
            )
            continue
        res = partition(hg, p, eps=eps, seed=seed)
        handle = PlannedSpGEMM(
            instance=inst,
            model=model,
            hypergraph=hg,
            partition=res,
            execution_plan=build_executable_plan(inst, model, res.parts, p),
            eps=eps,
            seed=seed,
        )
        # the handle's cost report is the single source for the per-model
        # numbers; this sweep only adds the cross-check volume plan, timing,
        # and (optionally) live execution
        report = handle.cost_report()
        vol_plan = build_volume_plan(hg, res.parts, p)
        rec = {
            "name": f"{inst.name}/select/{model}/p{p}",
            "model": model,
            "status": "ok",
            "us_per_call": int((time.time() - t0) * 1e6),
            "n_vertices": report["n_vertices"],
            "n_pins": report["n_pins"],
            "predicted_words": report["predicted_words"],
            "predicted_max_part": report["predicted_max_part"],
            "volume_plan_words": vol_plan.comm_words_ideal,
            "comp_imbalance": report["comp_imbalance"],
            "executable": spec.executable,
            # always surfaced (volume-plan fallback included) so benchmark
            # consumers get wire volume and message counts without
            # re-lowering: the alpha (messages) and padded-beta terms next
            # to the ideal words
            "padded_words": report["padded_words"],
            "planned_messages": report["planned_messages"],
        }
        assert rec["volume_plan_words"] == rec["predicted_words"], (
            f"{model}: volume plan diverged from connectivity metric"
        )
        if handle.execution_plan is not None:
            # sweep-historical names: measured_* == the report's planned_*
            rec["measured_words"] = report["planned_words"]
            if "planned_items" in report:
                # the unit count is the number of item transfers (e.g. row
                # shipments); the weighted count above is the useful words
                rec["measured_items"] = report["planned_items"]
            if execute and a_dense is not None:
                if can_exec:
                    rec.update(_execute(handle, a_dense, b_dense, want))
                else:
                    rec["exec"] = (
                        f"skipped ({device_count()} device(s) < p={p})"
                    )
        records.append(rec)
    ok = [r for r in records if r["status"] == "ok"]
    if ok:
        best = min(ok, key=lambda r: r["predicted_words"])
        for r in records:
            r["selected"] = r is best
    return records
