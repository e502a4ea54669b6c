"""Declarative model registry: one ``ModelSpec`` per paper model.

The paper's thesis is that a hypergraph partition IS an SpGEMM algorithm;
this module is where each algorithm's pieces are declared in one place
instead of being re-dispatched by name at three independent call sites
(``select.build_executable_plan``'s if/elif chain, ``runtime``'s per-model
packing branches, and the ``EXECUTABLE`` tuple).  A ``ModelSpec`` bundles:

- ``build``: the hypergraph builder (Sec. 5 / Def. 3.1, via ``core``);
- ``lower``: partition -> ``ExecutionPlan`` (pin-derived ownership so the
  planned words equal the model's connectivity prediction);
- ``mesh_shape`` / ``axis_names``: the process-grid geometry the executor
  wants — monoC's ``(2, p//2)`` (``(1, p)`` for odd p, including p=1) lives
  HERE, not at call sites;
- ``make_runner``: the value-time executor core (packing closure + step
  function) the compile-once runtime AOT-compiles;
- ``unpack`` / ``pack_values``: device-major shards <-> caller value layout;
- ``item_words`` / ``measured``: how the plan's routed words relate to the
  model's predicted words (exact, or useful-exact for rowwise's B rows).

All seven paper models are fully executable (lowerer + runner + unpacker).
Five run on the fine executor: columnwise, outer, monoA and monoB lower to
fine plans whose multiplications sit on the part of their coarse vertex
(B column j, inner index k, A or B nonzero), so only the net family the
model cuts is shipped.  Rowwise keeps its dense row executor, and monoC
its BSR step.  The registry also carries one entry that
is *not* a hypergraph model: ``"summa2d"``, the sparsity-oblivious Sparse
SUMMA baseline (``build is None`` — no hypergraph, no partition; the
lowerer goes straight from the instance).  It is excluded from
``model="auto"`` via ``in_auto=False`` so selection stays a contest among
the paper's models, with SUMMA always available as the competitor.

Everything jax-flavored is imported inside the runner factories so that
importing the registry (and therefore ``select``/``api``) stays light.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.core.spgemm_models import MODELS, SpGEMMInstance, build_model
from repro.distributed.plan_ir import (
    SCALAR_BLOCKS,
    ExecutionPlan,
    as_blocks,
    block_areas,
    build_fine_plan,
    build_monoC_plan,
    build_rowwise_plan,
    derive_owner_from_pins,
)
from repro.distributed.summa import _lower_summa, _summa_runner, summa_mesh_shape


# ---------------------------------------------------------------------------
# runner plumbing shared by the factories
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RunnerSetup:
    """What the compile-once runtime needs to AOT-compile one executor:
    a jit-compatible ``run(a_values, b_values, *tables) -> c_shards``
    function, the value shapes it was built for, the dense shape ``unpack``
    recovers, and ``tables``: the plan's integer index arrays (scatter
    indices, routes, pair lists) as host arrays.  The runtime uploads the
    tables once and passes them as device arguments on every call."""

    run: Callable
    a_shape: tuple[int, ...]
    b_shape: tuple[int, ...]
    out_shape: tuple[int, int]
    tables: tuple = ()


def vmap_batched_runner(make_runner: Callable) -> Callable:
    """Lift an unbatched runner factory to a batched one by ``jax.vmap``.

    The returned factory has the runner signature plus ``batch``: the
    compiled step maps over a leading batch axis on both value buffers, so
    one AOT executable streams ``batch`` same-structure multiplies per
    dispatch (multi-RHS products, MCL/AMG iterated chains).  This is the
    default ``ModelSpec.make_batched_runner`` — a spec whose step can't be
    vmapped (or has a faster hand-batched lowering) declares its own.
    """

    def make_batched(
        plan, a_structure, b_structure, mesh, *, batch, **kwargs
    ) -> RunnerSetup:
        import jax

        setup = make_runner(plan, a_structure, b_structure, mesh, **kwargs)
        return RunnerSetup(
            # values map over the batch axis; the tables are shared
            run=jax.vmap(setup.run, in_axes=(0, 0) + (None,) * len(setup.tables)),
            a_shape=(batch, *setup.a_shape),
            b_shape=(batch, *setup.b_shape),
            out_shape=setup.out_shape,
            tables=setup.tables,
        )

    return make_batched


def owner_slot(local_ids: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Invert a padded per-device id list into global-id -> (device, slot)
    lookup arrays (every id appears exactly once by construction)."""
    dev = np.empty(n, dtype=np.int64)
    slot = np.empty(n, dtype=np.int64)
    d, s = np.nonzero(local_ids >= 0)
    g = local_ids[d, s]
    dev[g] = d
    slot[g] = s
    return dev, slot


# ---------------------------------------------------------------------------
# plan lowerers (partition -> ExecutionPlan, pin-derived ownership)
# ---------------------------------------------------------------------------
def _lower_rowwise(inst: SpGEMMInstance, parts: np.ndarray, p: int) -> ExecutionPlan:
    I, K, _ = inst.shape
    acsc = inst.a_csc
    ks = np.repeat(np.arange(K, dtype=np.int64), np.diff(acsc.indptr))
    b_part = derive_owner_from_pins(ks, parts[acsc.indices.astype(np.int64)], K, p)
    return build_rowwise_plan(inst, parts, p, b_part=b_part)


def _lower_monoC(inst: SpGEMMInstance, parts: np.ndarray, p: int) -> ExecutionPlan:
    mult_dev = parts[inst.mult_c_pos]
    a_part = derive_owner_from_pins(inst.mult_a_pos, mult_dev, inst.a.nnz, p)
    b_part = derive_owner_from_pins(inst.mult_b_pos, mult_dev, inst.b.nnz, p)
    return build_monoC_plan(inst, parts, p, a_part=a_part, b_part=b_part)


def _lower_fine(
    inst: SpGEMMInstance, parts: np.ndarray, p: int, blocks=SCALAR_BLOCKS
) -> ExecutionPlan:
    return build_fine_plan(inst, parts, p, blocks=blocks)


# columnwise and outer run on the fine executor: each multiplication goes to
# the part of its 1D vertex (B column j or inner index k), the vertex's
# operands stay there, and the other nonzeros take the lowest part that
# needs them, so only the net family the model cuts is shipped and each cut
# net of connectivity lambda costs lambda - 1 times its net cost in words.
def _lower_columnwise(inst: SpGEMMInstance, parts: np.ndarray, p: int) -> ExecutionPlan:
    # only expand_a ships: a_ik to the parts of B row k's columns
    parts = np.asarray(parts, dtype=np.int64)
    _, b_col = inst.b.coo()
    plan = build_fine_plan(inst, parts[inst.mult_j], p, b_part=parts[b_col])
    plan.model = "columnwise"
    return plan


def _lower_outer(inst: SpGEMMInstance, parts: np.ndarray, p: int) -> ExecutionPlan:
    # only reduce_c ships: the partials of c_ij from the parts of its k's
    parts = np.asarray(parts, dtype=np.int64)
    _, a_col = inst.a.coo()
    b_row, _ = inst.b.coo()
    plan = build_fine_plan(
        inst, parts[inst.mult_k], p, a_part=parts[a_col], b_part=parts[b_row]
    )
    plan.model = "outer"
    return plan


def _lower_monoA(
    inst: SpGEMMInstance, parts: np.ndarray, p: int, blocks=SCALAR_BLOCKS
) -> ExecutionPlan:
    # monoA vertices are A nonzeros; colocating every multiplication with
    # its A nonzero makes expand_a empty, expand_b ship each b_kj to the
    # parts of A-column k (= the pins of B-net n^B_k, so items weighted by
    # the net's nnz(B row k) cost sum to exactly the B-net connectivity)
    # and reduce_c ship lambda - 1 partials per C net — measured == predicted
    parts = np.asarray(parts, dtype=np.int64)
    plan = build_fine_plan(inst, parts[inst.mult_a_pos], p, a_part=parts, blocks=blocks)
    plan.model = "monoA"
    return plan


def _lower_monoB(
    inst: SpGEMMInstance, parts: np.ndarray, p: int, blocks=SCALAR_BLOCKS
) -> ExecutionPlan:
    # symmetric to monoA with B stationary (vertices are B nonzeros in CSR
    # order, matching the monoB builder's pin convention)
    parts = np.asarray(parts, dtype=np.int64)
    plan = build_fine_plan(inst, parts[inst.mult_b_pos], p, b_part=parts, blocks=blocks)
    plan.model = "monoB"
    return plan


# ---------------------------------------------------------------------------
# runner factories (value-time executor cores; moved out of runtime's
# per-model branches — jax imported inside so the registry stays light)
# ---------------------------------------------------------------------------
def _rowwise_runner(plan, a_structure, b_structure, mesh, *, dtype, block, backend, axis, axes):
    import jax.numpy as jnp

    from repro.distributed import spgemm_exec as _exec

    p = plan.p
    I, K = a_structure.shape
    _, J = b_structure.shape
    if len(plan.ownership["a_row"]) != I or len(plan.ownership["b_row"]) != K:
        raise ValueError("plan was built for different operand shapes")
    ar, ac = a_structure.coo()
    br, bc = b_structure.coo()
    rdev, rslot = owner_slot(plan.local_ids["a_row"], I)
    bdev, bslot = owner_slot(plan.local_ids["b_row"], K)
    I_max = plan.local_ids["a_row"].shape[1]
    K_max = plan.local_ids["b_row"].shape[1]
    step, step_tables = _exec.make_rowwise_step(plan, mesh, K, J, axis=axis)

    def run(a_values, b_values, a_d, a_s, a_c, b_d, b_s, b_c, *tables):
        a_local = jnp.zeros((p, I_max, K), dtype).at[a_d, a_s, a_c].set(a_values)
        b_local = jnp.zeros((p, K_max, J), dtype).at[b_d, b_s, b_c].set(b_values)
        return step(a_local, b_local, *tables)

    tables = (rdev[ar], rslot[ar], ac, bdev[br], bslot[br], bc, *step_tables)
    return RunnerSetup(run, (a_structure.nnz,), (b_structure.nnz,), (I, J), tables)


def owned_nz_setup(
    plan, a_structure, b_structure, step, step_tables, *, dtype, item_shape=(), out_shape
) -> RunnerSetup:
    """Runner for executors whose operands live in owned nonzero slots
    (fine, monoA, monoB, monoC, summa2d): scatter the value vectors into
    device-major ``(p, N_max, *item_shape)`` tables (device scope
    ``repro.scatter_values``), then run ``step``."""
    import jax
    import jax.numpy as jnp

    p = plan.p
    nA, nB = a_structure.nnz, b_structure.nnz
    if nA != len(plan.a_part) or nB != len(plan.b_part):
        raise ValueError("plan was built for a different nonzero structure")
    adev, aslot = owner_slot(plan.local_ids["a_nz"], nA)
    bdev, bslot = owner_slot(plan.local_ids["b_nz"], nB)
    N_a = plan.local_ids["a_nz"].shape[1]
    N_b = plan.local_ids["b_nz"].shape[1]

    def run(a_values, b_values, a_d, a_s, b_d, b_s, *tables):
        with jax.named_scope("repro.scatter_values"):
            a_own = jnp.zeros((p, N_a, *item_shape), dtype).at[a_d, a_s].set(a_values)
            b_own = jnp.zeros((p, N_b, *item_shape), dtype).at[b_d, b_s].set(b_values)
        return step(a_own, b_own, *tables)

    return RunnerSetup(
        run,
        (nA, *item_shape),
        (nB, *item_shape),
        out_shape,
        (adev, aslot, bdev, bslot, *step_tables),
    )


def block_nz_setup(
    plan, a_structure, b_structure, step, step_tables, *, dtype, out_shape
) -> RunnerSetup:
    """Runner for the fine family over block operands (``plan.blocks`` =
    ((r, k), (k, c))).  Values arrive flat in block CSR order, (nnz*r*k,)
    and (nnz*k*c,), block after block: a host array of (nnz, 3, 3) blocks
    would be padded to the TPU's (8, 128) tiles in its last two dimensions
    on the way in.  On the device (scope ``repro.block_layout``) they turn
    items-major, (r*k, nnz); the scatter into the owned tables
    (``repro.scatter_values``) moves whole columns; and the step's
    items-major C, (p, r*c, slots), goes back flat and block-major,
    (p*slots*r*c,), for ``spgemm_exec.owned_c_values``."""
    import jax
    import jax.numpy as jnp

    p = plan.p
    nA, nB = a_structure.nnz, b_structure.nnz
    if nA != len(plan.a_part) or nB != len(plan.b_part):
        raise ValueError("plan was built for a different nonzero structure")
    items_a, items_b, _ = block_areas(plan.blocks)
    adev, aslot = owner_slot(plan.local_ids["a_nz"], nA)
    bdev, bslot = owner_slot(plan.local_ids["b_nz"], nB)
    N_a = plan.local_ids["a_nz"].shape[1]
    N_b = plan.local_ids["b_nz"].shape[1]

    def owned(values, n, items, n_max, col):
        with jax.named_scope("repro.block_layout"):
            by_item = values.reshape(n, items).T
        with jax.named_scope("repro.scatter_values"):
            tab = jnp.zeros((items, p * n_max), dtype).at[:, col].set(by_item)
            return tab.reshape(items, p, n_max).transpose(1, 0, 2)

    def run(a_values, b_values, a_col, b_col, *tables):
        a_own = owned(a_values, nA, items_a, N_a, a_col)
        b_own = owned(b_values, nB, items_b, N_b, b_col)
        c = step(a_own, b_own, *tables)
        with jax.named_scope("repro.block_layout"):
            return c.transpose(0, 2, 1).reshape(-1)

    return RunnerSetup(
        run,
        (nA * items_a,),
        (nB * items_b,),
        out_shape,
        # each nonzero's column in the (items, p * N_max) scatter target
        (adev * N_a + aslot, bdev * N_b + bslot, *step_tables),
    )


def _fine_runner(plan, a_structure, b_structure, mesh, *, dtype, block, backend, axis, axes):
    from repro.distributed import spgemm_exec as _exec

    step, tables = _exec.make_fine_step(plan, mesh, axis=axis)
    if plan.blocks != SCALAR_BLOCKS:
        (r, _), (_, c) = plan.blocks
        out_shape = (a_structure.shape[0] * r, b_structure.shape[1] * c)
        return block_nz_setup(
            plan, a_structure, b_structure, step, tables, dtype=dtype, out_shape=out_shape
        )
    out_shape = (a_structure.shape[0], b_structure.shape[1])
    return owned_nz_setup(
        plan, a_structure, b_structure, step, tables, dtype=dtype, out_shape=out_shape
    )


def _monoC_runner(plan, a_structure, b_structure, mesh, *, dtype, block, backend, axis, axes):
    # a_structure / b_structure are the BLOCK structures here; values are
    # (nnz, block, block) arrays in block CSR (= to_bsr) order
    from repro.distributed import spgemm_exec as _exec

    step, tables = _exec.make_monoC_step(plan, mesh, block=block, backend=backend, axes=axes)
    return owned_nz_setup(
        plan,
        a_structure,
        b_structure,
        step,
        tables,
        dtype=dtype,
        item_shape=(block, block),
        out_shape=(a_structure.shape[0] * block, b_structure.shape[1] * block),
    )


# ---------------------------------------------------------------------------
# unpackers (uniform signature; device-major shards -> dense array)
# ---------------------------------------------------------------------------
def _unpack_rowwise(c_local, plan, c_structure, shape):
    from repro.distributed.spgemm_exec import unpack_rowwise_result

    return unpack_rowwise_result(c_local, plan, shape[0])


def _unpack_monoC(c_local, plan, c_structure, shape):
    from repro.distributed.spgemm_exec import unpack_monoC_result

    return unpack_monoC_result(c_local, plan, c_structure, shape)


def _unpack_fine(c_local, plan, c_structure, shape):
    from repro.distributed.spgemm_exec import unpack_fine_result

    return unpack_fine_result(c_local, plan, c_structure, shape)


# ---------------------------------------------------------------------------
# value packing (canonical 1-D nonzero vectors -> executor value layout)
# ---------------------------------------------------------------------------
def _values_flat(vals: np.ndarray, block: int) -> np.ndarray:
    return vals


def _values_items(vals: np.ndarray, block: int) -> np.ndarray:
    # the fine family: scalars as they are, (nnz, r, k) blocks flattened
    # block after block (a view, no copy)
    vals = np.asarray(vals)
    return vals.reshape(-1) if vals.ndim == 3 else vals


def _values_blocked(vals: np.ndarray, block: int) -> np.ndarray:
    return np.asarray(vals).reshape(-1, block, block)


# ---------------------------------------------------------------------------
# mesh geometry
# ---------------------------------------------------------------------------
def _mesh_1d(p: int, inst: SpGEMMInstance | None = None) -> tuple[int, ...]:
    return (p,)


def _mesh_monoC(p: int, inst: SpGEMMInstance | None = None) -> tuple[int, ...]:
    # the executor flattens the 2D mesh for its all_to_alls, so any
    # factorization of p works; (1, p) covers odd p (and p=1) — the former
    # caller-side "odd p skipped" quirk is gone
    return (2, p // 2) if p % 2 == 0 and p > 1 else (1, p)


# ---------------------------------------------------------------------------
# the spec and the registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything one paper model needs, declared in one place.

    ``measured`` states how the plan's route-counted words relate to the
    model's prediction: "exact" (replicated-free plans — words on the
    wire == the predicted words), "useful" (rowwise: the unit-cost
    prediction recovered by nnz-weighting the shipped B rows), or None (no
    executor).

    ``build is None`` marks a partition-free baseline (summa2d): there is
    no hypergraph — the lowerer goes straight from the instance and the
    prediction is the plan's analytic ``stats["words_analytic"]``.
    ``in_auto`` gates membership in ``model="auto"`` selection; the SUMMA
    baseline is executable but never auto-selected."""

    name: str
    family: str  # "1D" | "2D" | "3D" (paper Sec. 5 classification)
    build: Callable | None  # (inst, include_nz=False) -> Hypergraph; None: no hypergraph
    lower: Callable | None = None  # (inst, parts, p) -> ExecutionPlan
    make_runner: Callable | None = None  # see RunnerSetup
    make_batched_runner: Callable | None = None  # (..., batch=n) -> RunnerSetup
    unpack: Callable | None = None  # (c_local, plan, c_structure, shape) -> dense
    mesh_shape: Callable = _mesh_1d  # (p, inst=None) -> process-grid shape
    axis_names: tuple[str, ...] = ("x",)
    pack_values: Callable = _values_flat  # (vals, block) -> executor layout
    item_words: Callable = lambda inst: None  # (inst) -> {route: words-per-item}
    needs_c_structure: bool = False  # unpack requires inst.c
    block_operands: bool = False  # plans and runs r x k . k x c block operands
    lower_include_nz: bool = False  # lowerer accepts include_nz partitions
    compile_defaults: dict = dataclasses.field(default_factory=dict)
    measured: str | None = None  # "exact" | "useful" | None
    in_auto: bool = True  # participates in model="auto" selection
    notes: str = ""

    @property
    def executable(self) -> bool:
        return self.lower is not None and self.make_runner is not None

    def make_setup(
        self, plan, a_structure, b_structure, mesh, *, batch=None, **kwargs
    ) -> RunnerSetup:
        """Build the executor core the runtime AOT-compiles.

        ``batch=None`` is the classic one-multiply step; ``batch=n`` returns
        the model's batched lowering (its declared ``make_batched_runner``,
        else the generic vmap lift) compiled for exactly ``n`` value sets.
        """
        if self.make_runner is None:
            raise ValueError(f"no runtime lowering for model {self.name!r}")
        if batch is None:
            return self.make_runner(plan, a_structure, b_structure, mesh, **kwargs)
        factory = self.make_batched_runner or vmap_batched_runner(self.make_runner)
        return factory(plan, a_structure, b_structure, mesh, batch=batch, **kwargs)

    def default_mesh(self, p: int, devices=None, instance=None):
        """Build the model's process grid over ``devices`` (default: the
        first p of ``jax.devices()``) — mesh geometry is a property of the
        algorithm, not of call sites.  ``instance`` lets shape hooks pick a
        non-square aspect from the operands (summa2d's ``(pr, pc)``)."""
        import jax
        from jax.sharding import Mesh

        devs = list(jax.devices() if devices is None else devices)
        if len(devs) < p:
            raise ValueError(
                f"{self.name} needs p={p} devices but only {len(devs)} available"
            )
        shape = self.mesh_shape(p, instance)
        return Mesh(np.array(devs[:p]).reshape(shape), self.axis_names)


def _build(model: str) -> Callable:
    def build(inst: SpGEMMInstance, include_nz: bool = False):
        return build_model(inst, model, include_nz=include_nz)

    return build


MODEL_SPECS: dict[str, ModelSpec] = {
    "fine": ModelSpec(
        name="fine",
        family="3D",
        build=_build("fine"),
        lower=_lower_fine,
        make_runner=_fine_runner,
        unpack=_unpack_fine,
        pack_values=_values_items,
        needs_c_structure=True,
        block_operands=True,
        # build_fine_plan adopts include_nz vertex placements as ownership
        lower_include_nz=True,
        measured="exact",
        notes="flop-level partition; expand-expand-reduce; words == connectivity",
    ),
    "rowwise": ModelSpec(
        name="rowwise",
        family="1D",
        build=_build("rowwise"),
        lower=_lower_rowwise,
        make_runner=_rowwise_runner,
        unpack=_unpack_rowwise,
        item_words=lambda inst: {"expand": inst.b.row_counts()},
        measured="useful",
        notes="ships whole B rows; nnz-weighted route words == prediction",
    ),
    "columnwise": ModelSpec(
        name="columnwise",
        family="1D",
        build=_build("columnwise"),
        lower=_lower_columnwise,
        make_runner=_fine_runner,
        unpack=_unpack_fine,
        pack_values=_values_items,
        needs_c_structure=True,
        measured="exact",
        notes="B column stationary; mults on their column's part, fine executor",
    ),
    "outer": ModelSpec(
        name="outer",
        family="1D",
        build=_build("outer"),
        lower=_lower_outer,
        make_runner=_fine_runner,
        unpack=_unpack_fine,
        pack_values=_values_items,
        needs_c_structure=True,
        measured="exact",
        notes="A column and B row k stationary; only C partials ship, fine executor",
    ),
    "monoA": ModelSpec(
        name="monoA",
        family="2D",
        build=_build("monoA"),
        lower=_lower_monoA,
        make_runner=_fine_runner,
        unpack=_unpack_fine,
        pack_values=_values_items,
        needs_c_structure=True,
        block_operands=True,
        measured="exact",
        notes="A nonzero stationary; mults colocated with A, fine executor",
    ),
    "monoB": ModelSpec(
        name="monoB",
        family="2D",
        build=_build("monoB"),
        lower=_lower_monoB,
        make_runner=_fine_runner,
        unpack=_unpack_fine,
        pack_values=_values_items,
        needs_c_structure=True,
        block_operands=True,
        measured="exact",
        notes="B nonzero stationary; mults colocated with B, fine executor",
    ),
    "monoC": ModelSpec(
        name="monoC",
        family="2D",
        build=_build("monoC"),
        lower=_lower_monoC,
        make_runner=_monoC_runner,
        unpack=_unpack_monoC,
        mesh_shape=_mesh_monoC,
        axis_names=("x", "y"),
        pack_values=_values_blocked,
        needs_c_structure=True,
        # the front door plans scalar instances (block=1): the BSR kernel
        # would run one grid step per multiplication on 1x1 tiles, each
        # padded to a 128-lane row (512 B per scalar operand on TPU), while
        # the XLA gather/einsum/segment-add path keeps scalars as flat
        # vectors; backend="pallas" stays available per compile()
        compile_defaults={"backend": "xla"},
        measured="exact",
        notes="C nonzero lives on one device; 2D mesh, BSR local compute",
    ),
    # -- not a hypergraph model: the oblivious competitor ------------------
    "summa2d": ModelSpec(
        name="summa2d",
        family="2D",
        build=None,
        lower=_lower_summa,
        make_runner=_summa_runner,
        unpack=_unpack_monoC,  # same device-major owned-C slot layout
        mesh_shape=summa_mesh_shape,
        axis_names=("x", "y"),
        pack_values=_values_blocked,
        needs_c_structure=True,
        # same reason as monoC: scalar (1x1) blocks, one kernel grid step
        # and one lane-padded tile per multiplication
        compile_defaults={"backend": "xla"},
        measured="exact",
        in_auto=False,
        notes="Sparse SUMMA (Buluc-Gilbert): sparsity-oblivious 2D baseline",
    ),
}

assert set(MODELS) <= set(MODEL_SPECS), "registry out of sync with core MODELS"


def get_spec(model: str) -> ModelSpec:
    try:
        return MODEL_SPECS[model]
    except KeyError:
        raise ValueError(
            f"unknown model {model!r}; choose from {tuple(MODEL_SPECS)}"
        ) from None


def checked_blocks(model: str, blocks) -> tuple:
    """``blocks`` as ((r, k), (k, c)) (``plan_ir.as_blocks``).  A model
    whose executor cannot run block operands (all but the fine family, and
    "auto", which would choose among them) refuses any but 1 x 1, by name."""
    blocks = as_blocks(blocks)
    if blocks != SCALAR_BLOCKS and (model == "auto" or not get_spec(model).block_operands):
        takes = tuple(n for n, s in MODEL_SPECS.items() if s.block_operands)
        raise ValueError(
            f"model {model!r} does not take block operands {blocks}; "
            f"the models that do: {takes}"
        )
    return blocks


def executable_models() -> tuple[str, ...]:
    """Names of the paper models with a full plan-lowering + executor path
    that participate in ``model="auto"``, in ``MODELS`` order (the summa2d
    baseline is executable but excluded via ``in_auto=False``)."""
    return tuple(
        n for n in MODELS if MODEL_SPECS[n].executable and MODEL_SPECS[n].in_auto
    )
