"""One front door: ``repro.plan(A, B, p=8)`` — partition to product.

The paper's thesis is that a hypergraph partition IS an SpGEMM algorithm.
Using the library used to mean hand-stitching five layers —
``SpGEMMInstance`` -> ``build_model`` -> ``partition`` ->
plan lowering -> ``compile_spgemm`` — with model-specific folklore (monoC's
2D mesh, per-model value layouts, dtype promotion) known only to
``select._execute``.  This module is the stable public pipeline over the
declarative ``ModelSpec`` registry:

    import repro

    spgemm = repro.plan(A, B, p=8, model="auto", eps=0.10, seed=0)
    spgemm.cost_report()             # predicted / planned / padded words
    exe = spgemm.compile()           # mesh + dtype + backend per ModelSpec
    C = exe(a_vals, b_vals)          # dense C, == A @ B
    C = spgemm(a_vals, b_vals)       # same, compile-on-first-use

``A`` / ``B`` are structures (dense array, scipy sparse, or
``SparseStructure``); values are 1-D nonzero vectors in canonical CSR order
for *every* model — the registry's ``pack_values`` hides monoC's block
layout.  ``model="auto"`` partitions every executable model and keeps the
communication-minimal one (the same min-predicted-words rule the
``select.sweep_instance`` report applies, scoped to the models that can
actually run).

Everything jax-flavored is imported lazily so that planning (a pure
numpy/scipy affair) works — and stays fast to import — without touching a
device.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import PartitionResult, evaluate
from repro.core import partition as _partition
from repro.core.comm import (
    CommCosts,
    memory_dependent_bound,
    memory_independent_bound,
)
from repro.core.hypergraph import Hypergraph
from repro.core.spgemm_models import SpGEMMInstance, block_net_costs
from repro.distributed.plan_ir import (
    SCALAR_BLOCKS,
    ExecutionPlan,
    block_areas,
    build_volume_plan,
    measured_route_words,
    route_messages,
)
from repro.distributed.registry import (
    MODEL_SPECS,
    ModelSpec,
    checked_blocks,
    executable_models,
    get_spec,
)
__all__ = [
    "CompiledSpGEMM",
    "PlannedSpGEMM",
    "device_count",
    "plan",
    "session",
]


def device_count() -> int:
    """Devices visible to this process (the one place jax is asked — the
    sweep, the executors and the examples all route through here)."""
    import jax

    return jax.device_count()


# ---------------------------------------------------------------------------
# the compiled handle
# ---------------------------------------------------------------------------
class CompiledSpGEMM:
    """A compiled SpGEMM pipeline: canonical values in, dense C out.

    Wraps the runtime's AOT executable with the model's value packing and
    unpacking so every model takes 1-D nonzero value vectors (canonical CSR
    order of the planned structures) and returns the dense (I, J) product —
    no caller-visible mesh, dtype, block or layout special-casing.  The raw
    device-shard interface stays available as ``.runtime``.

    A handle compiled with ``batch=n`` streams value *batches*: inputs are
    (m, nnz) arrays with ``1 <= m <= batch_capacity`` (the bucketed
    capacity), the output is (m, I, J).  Ragged batches are zero-padded up
    to the capacity on the way in and trimmed on the way out, so every
    batch size within one bucket hits the same AOT executable.
    """

    def __init__(
        self,
        planned: "PlannedSpGEMM",
        runtime_exe,
        spec: ModelSpec,
        out_shape: tuple[int, int] | None = None,
    ):
        self.planned = planned
        self.runtime = runtime_exe
        self.spec = spec
        if out_shape is None:
            I, _, J = planned.instance.shape
            (r, _), (_, c) = planned.execution_plan.blocks
            out_shape = (I * r, J * c)
        self._out = tuple(out_shape)

    @property
    def mesh(self):
        return self.runtime.mesh

    @property
    def dtype(self):
        return self.runtime.dtype

    @property
    def batch_capacity(self) -> int | None:
        """Batch slots the executor was compiled for (None: unbatched)."""
        return self.runtime.batch

    @property
    def cost_model_words(self) -> tuple[int, int]:
        """(ideal, padded) words per call, from the plan's routes."""
        return self.runtime.cost_model_words

    def pack(self, a_values, b_values) -> tuple[np.ndarray, np.ndarray]:
        """Canonical 1-D nonzero vectors -> the executor's value layout.

        For a batched handle the inputs are (m, nnz) stacks; each row is
        packed independently and the stack is zero-padded to the compiled
        batch capacity (padding rows cost device flops, never correctness —
        their products are simply dropped by ``__call__``).  Runs inside
        the host span ``repro.pack``.
        """
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("repro.pack"):
            return self._pack(a_values, b_values)

    def _pack(self, a_values, b_values):
        block = self.runtime.block
        if self.batch_capacity is None:
            return (
                self.spec.pack_values(np.asarray(a_values), block),
                self.spec.pack_values(np.asarray(b_values), block),
            )
        cap = self.batch_capacity

        def pack_stack(values, name):
            values = np.atleast_2d(np.asarray(values))
            m = values.shape[0]
            if not 1 <= m <= cap:
                raise ValueError(
                    f"{name} batch of {m} exceeds the compiled capacity {cap}; "
                    f"recompile with batch={m} (bucketed) or split the batch"
                )
            packed = np.stack(
                [self.spec.pack_values(values[i], block) for i in range(m)]
            )
            if m < cap:
                pad = np.zeros((cap - m, *packed.shape[1:]), packed.dtype)
                packed = np.concatenate([packed, pad])
            return packed, m

        a, m_a = pack_stack(a_values, "A")
        b, m_b = pack_stack(b_values, "B")
        if m_a != m_b:
            raise ValueError(f"A batch ({m_a}) and B batch ({m_b}) disagree")
        return a, b

    def __call__(self, a_values, b_values) -> np.ndarray:
        I, J = self._out
        if self.batch_capacity is None:
            a, b = self.pack(a_values, b_values)
            return np.asarray(self.runtime.unpack(self.runtime(a, b)))[:I, :J]
        m = np.atleast_2d(np.asarray(a_values)).shape[0]
        a, b = self.pack(a_values, b_values)
        c_local = np.asarray(self.runtime(a, b))[:m]
        return self.runtime.unpack(c_local)[:, :I, :J]


# ---------------------------------------------------------------------------
# the planned handle
# ---------------------------------------------------------------------------
@dataclasses.dataclass(eq=False)  # identity semantics: fields hold ndarrays
class PlannedSpGEMM:
    """One partition-is-the-algorithm pipeline, planned and ready.

    Owns the instance, the model hypergraph, the ``PartitionResult`` and
    (for executable models) the lowered ``ExecutionPlan``.  ``compile()``
    builds the model's process grid and AOT-compiles the executor;
    ``execute``/``__call__`` go straight from canonical nonzero values to
    the dense product, compiling on first use (cached thereafter).
    """

    instance: SpGEMMInstance
    model: str
    # None for partition-free baselines (summa2d): no hypergraph was built
    # and no partition ran — the execution plan is the whole story
    hypergraph: Hypergraph | None
    partition: PartitionResult | None
    execution_plan: ExecutionPlan | None
    eps: float = 0.10
    seed: int = 0
    selection: list[dict] | None = None  # model="auto" sweep records

    @property
    def spec(self) -> ModelSpec:
        return get_spec(self.model)

    @property
    def p(self) -> int:
        if self.partition is not None:
            return self.partition.p
        return self.execution_plan.p

    @property
    def executable(self) -> bool:
        return self.execution_plan is not None

    def costs(self) -> CommCosts:
        """The partition's communication metrics (Lemma 4.2 machinery)."""
        if self.hypergraph is None:
            raise ValueError(
                f"model {self.model!r} is partition-free (no hypergraph); "
                f"its communication is the analytic cost_report()"
            )
        return evaluate(self.hypergraph, self.partition.parts, self.p)

    def cost_report(self) -> dict:
        """Predicted vs planned vs padded words, plus the eq. (1) bounds.

        - ``predicted_words``: the connectivity metric the partitioner
          minimized (sum over cut nets of c(n) * (lambda(n) - 1));
        - ``planned_words``: the words the lowered plan's routing tables
          actually schedule (transfer enumeration — an independent code
          path), item-weighted per the model's convention;
        - ``padded_words``: what the padded all_to_all slots move on the
          wire;
        - ``planned_messages``: non-empty (src, dst) route cells — the
          alpha term next to the words' beta term;
        - ``bounds``: the classical eq. (1) lower bounds the paper compares
          against (local memory taken as 3 * nnz / p, the bench convention).

        For a partition-free baseline (summa2d) ``predicted_words`` is the
        closed-form analytic volume (``stats["words_analytic"]``) and
        ``planned_words`` the route-table count — their equality is the
        same measured == predicted check, with connectivity replaced by
        the closed form.
        """
        inst, p = self.instance, self.p
        n_nz = inst.a.nnz + inst.b.nnz + inst.c.nnz
        local_mem = max(3 * n_nz / p, 64)
        report = {
            "model": self.model,
            "p": p,
            "executable": self.executable,
            "bounds": {
                "memory_dependent": round(
                    memory_dependent_bound(inst.n_mult, p, local_mem), 1
                ),
                "memory_independent": round(
                    memory_independent_bound(inst.n_mult, n_nz, p), 1
                ),
            },
        }
        if self.hypergraph is None:
            plan_obj = self.execution_plan
            report["predicted_words"] = int(plan_obj.stats["words_analytic"])
            report["planned_words"] = measured_route_words(plan_obj)
            report["padded_words"] = plan_obj.comm_words_padded
            report["planned_messages"] = route_messages(plan_obj)
            return report
        costs = self.costs()
        report.update(
            {
                "n_vertices": self.hypergraph.n_vertices,
                "n_pins": self.hypergraph.n_pins,
                "predicted_words": int(costs.connectivity),
                "predicted_max_part": int(costs.max_part_cost),
                "expand_words": int(costs.expand),
                "fold_words": int(costs.fold),
                "comp_imbalance": round(costs.comp_imbalance, 4),
            }
        )
        plan_obj = self.execution_plan
        if plan_obj is None:
            # plans that didn't lower (include_nz partitions on models whose
            # lowerers don't accept them) still get an IR whose words ==
            # prediction (net costs ride on the routes' per-item overrides)
            plan_obj = build_volume_plan(self.hypergraph, self.partition.parts, p)
            report["planned_words"] = plan_obj.comm_words_ideal
        else:
            item_words = self.spec.item_words(inst)
            report["planned_words"] = measured_route_words(plan_obj, item_words)
            if item_words is not None:
                report["planned_items"] = measured_route_words(plan_obj)
        report["padded_words"] = plan_obj.comm_words_padded
        report["planned_messages"] = route_messages(plan_obj)
        return report

    def compile(
        self,
        devices=None,
        dtype=np.float32,
        backend: str | None = None,
        batch: int | None = None,
    ) -> CompiledSpGEMM:
        """AOT-compile the pipeline's executor.

        The process grid comes from the model's ``ModelSpec`` (monoC gets
        its 2D mesh, including the odd-p fallback, without the caller ever
        seeing it), as do backend defaults; ``devices`` optionally pins the
        device set (default: the first p of ``jax.devices()``).

        ``batch=n`` compiles the *batched* step: the registered runner is
        vmapped over a leading value-batch axis so up to ``n`` same-structure
        multiplies stream through one dispatch (multi-RHS, MCL/AMG iterated
        chains).  ``n`` is rounded up to a geometric capacity bucket
        (``runtime.batch_bucket``) so ragged request batches share one AOT
        executable; the handle pads and trims transparently.
        """
        if self.execution_plan is None:
            if self.spec.executable:
                raise ValueError(
                    f"model {self.model!r} was planned with include_nz=True "
                    f"but its lowerer does not accept V^nz partitions; "
                    f"replan with include_nz=False to execute"
                )
            raise ValueError(
                f"model {self.model!r} is volume-only (predicts, never "
                f"executes); executable models: {executable_models()}"
            )
        from repro.distributed.runtime import batch_bucket, compile_spgemm

        spec = self.spec
        inst = self.instance
        mesh = spec.default_mesh(self.p, devices, instance=inst)
        if backend is None:
            backend = spec.compile_defaults.get("backend")
        runtime_exe = compile_spgemm(
            self.execution_plan,
            inst.a,
            inst.b,
            mesh,
            dtype=dtype,
            backend=backend,
            block=spec.compile_defaults.get("block", 1),
            c_structure=inst.c,
            batch=None if batch is None else batch_bucket(batch),
        )
        return CompiledSpGEMM(self, runtime_exe, spec)

    def execute(self, a_values, b_values, **compile_kwargs) -> np.ndarray:
        """Canonical nonzero values in, dense C out.

        Compiles on first use (the runtime LRU makes repeat calls hit the
        same AOT executable); dtype defaults to the promoted value dtype.
        """
        a_values = np.asarray(a_values)
        b_values = np.asarray(b_values)
        compile_kwargs.setdefault(
            "dtype", np.promote_types(a_values.dtype, b_values.dtype)
        )
        return self.compile(**compile_kwargs)(a_values, b_values)

    __call__ = execute


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------
def _plan_one(
    inst: SpGEMMInstance,
    model: str,
    p: int,
    eps: float,
    seed: int,
    include_nz: bool,
    engine: str = "flat",
    warm_start: np.ndarray | None = None,
    warm_drift_limit: float = 0.5,
    coarsen: str = "auto",
    blocks=SCALAR_BLOCKS,
) -> PlannedSpGEMM:
    spec = get_spec(model)
    blocks = checked_blocks(model, blocks)
    blocked = blocks != SCALAR_BLOCKS
    if spec.build is None:
        # partition-free baseline (summa2d): no hypergraph to build or
        # partition — lower the instance straight to its execution plan
        return PlannedSpGEMM(
            instance=inst,
            model=model,
            hypergraph=None,
            partition=None,
            execution_plan=spec.lower(inst, None, p),
            eps=eps,
            seed=seed,
        )
    hg = spec.build(inst, include_nz=include_nz)
    if blocked:
        hg = block_net_costs(hg, block_areas(blocks))
    res = _partition(
        hg,
        p,
        eps=eps,
        seed=seed,
        engine=engine,
        warm_start=warm_start,
        warm_drift_limit=warm_drift_limit,
        coarsen=coarsen,
    )
    plan_obj = None
    if spec.lower is not None and (not include_nz or spec.lower_include_nz):
        lower_blocks = {"blocks": blocks} if blocked else {}
        plan_obj = spec.lower(inst, res.parts, p, **lower_blocks)
    return PlannedSpGEMM(
        instance=inst,
        model=model,
        hypergraph=hg,
        partition=res,
        execution_plan=plan_obj,
        eps=eps,
        seed=seed,
    )


def plan(
    A,
    B=None,
    p: int = 8,
    model: str = "auto",
    eps: float = 0.10,
    seed: int = 0,
    name: str = "",
    include_nz: bool = False,
    engine: str = "flat",
    coarsen: str = "auto",
    blocks=None,
) -> PlannedSpGEMM:
    """Plan a distributed SpGEMM: model the instance, partition, lower.

    ``A`` / ``B`` give the nonzero structures (dense array, scipy sparse
    matrix, or ``SparseStructure`` — values never enter the inspector);
    alternatively ``A`` may be an existing ``SpGEMMInstance`` (``B`` omitted)
    so repeated per-model planning reuses one symbolic inspection.
    ``model`` is one of the paper's seven (``repro.MODELS``, all
    executable), ``"summa2d"`` (the sparsity-oblivious Sparse SUMMA
    baseline — partition-free, never auto-selected), or ``"auto"``:
    partition every auto-eligible model and keep the communication-minimal
    one (the same min-predicted-words rule ``sweep_instance`` reports); the
    per-model records land on ``.selection``.
    ``include_nz`` keeps the V^nz nonzero vertices (Sec. 4 reading); the
    partitioner then places them too, and the handle stays cost/analysis-
    only unless the model's lowerer understands such partitions (fine does).
    ``engine`` selects the partitioner engine (``"flat"`` host default,
    ``"device"`` for the batched jax engine above its size threshold,
    ``"loop"`` for the per-move reference — see DESIGN.md §6); it changes
    planning *speed*, not the plan contract.  ``coarsen`` picks the
    ``engine="device"`` descend (``"auto"``/``"device"`` keep the V-cycle
    device-resident, ``"host"`` forces the host-scipy descend) and is
    ignored by the host engines.

    ``blocks=((r, k), (k, c))`` plans block-sparse operands: ``A`` / ``B``
    are then the block structures, every nonzero an r x k block of A or a
    k x c block of B, values are (nnz, r, k) / (nnz, k, c) arrays in block
    CSR order and C's are (nnz(C), r, c).  The fine executor family (fine,
    monoA, monoB) takes them, its net costs and route words weighted by the
    block areas; any other model, ``"auto"`` included, refuses them.
    """
    if isinstance(A, SpGEMMInstance):
        if B is not None:
            raise ValueError("B must be omitted when A is an SpGEMMInstance")
        inst = A
    else:
        if B is None:
            raise ValueError("B is required unless A is an SpGEMMInstance")
        inst = SpGEMMInstance.from_operands(A, B, name=name)
    if model != "auto":
        if model not in MODEL_SPECS:
            raise ValueError(
                f"unknown model {model!r}; choose from "
                f"{tuple(MODEL_SPECS)} or 'auto'"
            )
        return _plan_one(
            inst, model, p, eps, seed, include_nz, engine, coarsen=coarsen,
            blocks=blocks,
        )
    checked_blocks(model, blocks)
    candidates = [
        _plan_one(inst, m, p, eps, seed, include_nz, engine, coarsen=coarsen)
        for m in executable_models()
    ]
    records = []
    for cand in candidates:
        rec = cand.cost_report()
        rec["selected"] = False
        records.append(rec)
    # auto means "pick something that can run": with include_nz only some
    # lowerers accept the partition, so restrict to those when any exist
    viable = [i for i, c in enumerate(candidates) if c.execution_plan is not None]
    pool = viable or range(len(candidates))
    best = min(pool, key=lambda i: records[i]["predicted_words"])
    records[best]["selected"] = True
    chosen = candidates[best]
    chosen.selection = records
    return chosen


def session(
    p: int = 8,
    model: str = "auto",
    eps: float = 0.10,
    seed: int = 0,
    engine: str = "flat",
    store_dir: str | None = None,
    policy=None,
    **kwargs,
):
    """A resilient handle for iterated, structure-drifting SpGEMM.

    ``repro.session(p=8)`` returns a ``SpGEMMSession``: call it like
    ``plan(...)`` would be called per structure, but across a loop —
    ``sess.multiply(A, B)`` fingerprints the operands, reuses the warm
    executor when the structure is unchanged, warm-start-replans on drift,
    persists plans under ``store_dir`` (a restarted session rebuilds its
    pool from there), and retries/downgrades through ``policy`` (a
    ``repro.FaultPolicy``) on stage failures.  ``blocks=((r, k), (k, c))``
    (a keyword argument) plans block operands as ``plan`` does.  See
    ``repro.distributed.session`` for the full contract.
    """
    from repro.distributed.session import SpGEMMSession

    return SpGEMMSession(
        p=p,
        model=model,
        eps=eps,
        seed=seed,
        engine=engine,
        store_dir=store_dir,
        policy=policy,
        **kwargs,
    )
