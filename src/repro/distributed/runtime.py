"""Compile-once executor runtime: cached AOT executors, value-only updates.

The paper's amortization premise is that the partition — and therefore the
plan — is computed once and reused across many multiplications with the same
sparsity structure (AMG applies one partition across repeated Galerkin
products; MCL squares a same-structure matrix every iteration).  The
executors in ``spgemm_exec`` realize the plans correctly but, called naively
on dense operands, pay the full inspector bill on every invocation: dense ->
sparse round trips, per-call route-table uploads, and a fresh shard_map
trace + XLA compile per call (the executor closures are rebuilt each time,
so nothing caches).

``CompiledSpGEMM`` does all structure-time work exactly once per
(plan, operand structure, mesh, dtype, backend):

- host packing collapses to one vectorized owner/slot scatter-spec (the
  ``np.nonzero(local_ids >= 0)`` idiom), computed at construction;
- route tables, pair lists and scatter indices are uploaded to the devices
  once, with the shardings the compiled program asks for, and passed as
  arguments on every call (baked in as constants, they made compile time
  and the executable grow with the plan);
- the whole executor (value scatter -> expand -> local compute -> reduce)
  is AOT-compiled via ``jax.jit(...).lower().compile()`` with the value
  buffers donated, so ``__call__(a_values, b_values)`` does zero host
  structure work and zero retracing — the steady-state cost is exactly the
  collectives plus local compute the plan prescribes.

Which packing closure, step builder and unpacker a plan gets is no longer
decided here: ``registry.ModelSpec.make_runner`` / ``.unpack`` are the single
declarative source — this module only owns the model-agnostic machinery
(fingerprints, AOT compile, donation, the bounded LRU).

Value conventions (``__call__`` inputs):

- rowwise and the fine family (fine, columnwise, outer, monoA, monoB): 1-D
  nonzero value vectors in the operands' canonical CSR order
  (``SparseStructure`` order — what ``structure_and_values`` returns);
- monoC: (nnz, b, b) block-value arrays in the *block* structure's CSR
  order (``to_bsr(...).blocks`` order).  The ``repro.api`` front door hides
  this behind ``ModelSpec.pack_values``.

``compile_spgemm`` memoizes executors in a bounded LRU keyed on
(plan fingerprint, structure fingerprints, mesh, dtype, backend, block,
axis names); the dense entry points in ``spgemm_exec`` are thin wrappers
that hit this cache on every same-structure call.  ``trace_count()`` exposes
a retrace counter so tests can pin "zero recompiles after warmup".
"""
from __future__ import annotations

import hashlib
import os
import warnings
from collections import OrderedDict

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.distributed.registry import get_spec
from repro.sparse.structure import (
    SparseStructure,
    structure_and_values,
    structure_fingerprint,
)
from repro.testing import faults

__all__ = [
    "CompiledSpGEMM",
    "batch_bucket",
    "compile_spgemm",
    "cache_clear",
    "cache_info",
    "plan_fingerprint",
    "structure_and_values",
    "structure_fingerprint",
    "trace_count",
]

# -- batch-size bucketing ----------------------------------------------------
#: geometric batch-capacity buckets (x2 from 1).  A batched executor is
#: compiled for a bucket CAPACITY, not a request count: ragged request
#: batches pad up to the same capacity and hit the same AOT executable —
#: the serving loop never retraces on batch-size jitter (the same idea as
#: the device partitioner's x1.5 shape buckets, PR 6).
BATCH_GROWTH = 2


def batch_bucket(n: int) -> int:
    """Smallest batch-capacity bucket holding ``n`` items (1, 2, 4, 8, ...)."""
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    b = 1
    while b < n:
        b *= BATCH_GROWTH
    return b

# -- retrace accounting ------------------------------------------------------
_TRACE_COUNT = 0


def trace_count() -> int:
    """Number of times any runtime executor body has been traced (== number
    of AOT compiles).  Stable across ``CompiledSpGEMM.__call__`` — the test
    hook for the zero-retrace claim."""
    return _TRACE_COUNT


def _mark_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


# -- fingerprints ------------------------------------------------------------
def plan_fingerprint(plan) -> str:
    """Content hash of a plan's executor-visible state, computed once and
    memoized on the plan object (id-stable: repeat lookups are O(1))."""
    fp = getattr(plan, "_fingerprint", None)
    if fp is None:
        h = hashlib.sha1(f"{plan.model}/{plan.p}/{plan.blocks}".encode())
        for tag, group in (
            ("own", plan.ownership),
            ("loc", plan.local_ids),
            ("cmp", plan.compute),
        ):
            for k in sorted(group):
                h.update(f"{tag}:{k}".encode())
                h.update(np.ascontiguousarray(group[k]))
        for k in sorted(plan.routes):
            r = plan.routes[k]
            h.update(f"route:{k}:{r.word_size}".encode())
            h.update(np.ascontiguousarray(r.send_idx))
            h.update(np.ascontiguousarray(r.recv_key))
        fp = h.hexdigest()
        plan._fingerprint = fp
    return fp


def _mesh_key(mesh: Mesh) -> tuple:
    return (
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


# -- the compiled executor ---------------------------------------------------
class CompiledSpGEMM:
    """One AOT-compiled SpGEMM executor: structure work done, values only.

    Construction performs every structure-dependent step (scatter-spec
    build, constant upload, trace, lowering, XLA compile) by handing the
    plan's ``ModelSpec.make_runner`` the operand structures; ``__call__``
    takes nonzero value vectors and returns the executor's device-major
    C shards with no host structure work and no retracing.
    """

    def __init__(
        self,
        plan,
        a_structure: SparseStructure,
        b_structure: SparseStructure,
        mesh: Mesh,
        *,
        dtype=np.float32,
        backend: str | None = None,
        block: int = 1,
        axis: str = "x",
        axes: tuple[str, str] = ("x", "y"),
        c_structure: SparseStructure | None = None,
        batch: int | None = None,
    ):
        faults.fire("compile")
        if mesh.devices.size != plan.p:
            raise ValueError(
                f"plan is for p={plan.p} but mesh has {mesh.devices.size} devices"
            )
        if a_structure.shape[1] != b_structure.shape[0]:
            raise ValueError(
                f"inner dimensions disagree: {a_structure.shape} @ {b_structure.shape}"
            )
        if batch is not None and batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.plan = plan
        self.model = plan.model
        self.mesh = mesh
        self.dtype = np.dtype(dtype)
        self.block = block
        self.backend = backend
        self.c_structure = c_structure
        self.batch = batch
        dt = self.dtype

        spec = get_spec(plan.model)
        if spec.make_runner is None:
            raise ValueError(f"no runtime lowering for model {plan.model!r}")
        self.spec = spec
        setup = spec.make_setup(
            plan,
            a_structure,
            b_structure,
            mesh,
            dtype=dt,
            block=block,
            backend=backend,
            axis=axis,
            axes=axes,
            batch=batch,
        )
        self._I, self._J = setup.out_shape
        self._a_shape, self._b_shape = setup.a_shape, setup.b_shape
        run = setup.run
        # index tables ride as int32 (jax runs without x64)
        tables = [np.asarray(t, dtype=np.int32) for t in setup.tables]

        def traced(a_values, b_values, *tables):
            _mark_trace()
            return run(a_values, b_values, *tables)

        with warnings.catch_warnings():
            # donation is best-effort: backends without it (CPU) warn per
            # compile, which would spam every cache miss
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            self._compiled = (
                jax.jit(traced, donate_argnums=(0, 1))
                .lower(
                    jax.ShapeDtypeStruct(setup.a_shape, dt),
                    jax.ShapeDtypeStruct(setup.b_shape, dt),
                    *(jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables),
                )
                .compile()
            )
        # upload once, placed as the program wants them: no per-call transfer
        in_shardings = self._compiled.input_shardings[0][2:]
        self._tables = tuple(
            jax.device_put(t, sh) for t, sh in zip(tables, in_shardings)
        )

    def _coerce(self, x, shape, name: str):
        if isinstance(x, jax.Array):
            if x.dtype != self.dtype:
                x = x.astype(self.dtype)
        else:
            # host values stay numpy: the executable uploads a fresh buffer,
            # so donation never invalidates a caller-held array
            x = np.asarray(x, dtype=self.dtype)
        if x.shape != shape:
            raise ValueError(
                f"{name} values have shape {x.shape}, but this executor was "
                f"compiled for {shape} — same-structure updates only"
            )
        return x

    def __call__(self, a_values, b_values) -> jax.Array:
        """Value-only update: returns device-major C shards (the same layout
        the underlying ``*_spgemm`` executor returns; a leading batch axis
        when compiled with ``batch=n``).  Passing a jax.Array transfers
        ownership of its buffer (donation).  The host span ``repro.call``
        covers the coercion and the dispatch, which enqueues the
        host-to-device copy; it waits for neither the copy nor the step."""
        faults.fire("execute")
        with TraceAnnotation("repro.call"):
            a = self._coerce(a_values, self._a_shape, "A")
            b = self._coerce(b_values, self._b_shape, "B")
            return self._compiled(a, b, *self._tables)

    def unpack(self, c_local) -> np.ndarray:
        """Scatter device-major C shards back to a dense (I, J) array (padded
        block-grid shape for monoC) via the model's registered unpacker.  A
        batched executor's shards carry a leading batch axis and unpack to
        (batch, I, J)."""
        if self.spec.needs_c_structure and self.c_structure is None:
            raise ValueError(f"unpacking a {self.model} result needs c_structure")
        shape = (self._I, self._J)
        if self.batch is None:
            return self.spec.unpack(c_local, self.plan, self.c_structure, shape)
        c_local = np.asarray(c_local)
        return np.stack(
            [
                self.spec.unpack(c_local[i], self.plan, self.c_structure, shape)
                for i in range(c_local.shape[0])
            ]
        )

    @property
    def cost_model_words(self) -> tuple[int, int]:
        """(ideal, padded) words per call — what the partition promised and
        what the padded routes actually move."""
        return self.plan.comm_words_ideal, self.plan.comm_words_padded


# -- bounded LRU cache -------------------------------------------------------
CACHE_SIZE = int(os.environ.get("REPRO_EXEC_CACHE_SIZE", "16"))
_CACHE: OrderedDict[tuple, CompiledSpGEMM] = OrderedDict()
_STATS = {"hits": 0, "misses": 0}


def _cache_key(
    plan, a_structure, b_structure, mesh, dtype, backend, block, axis, axes, batch
):
    return (
        plan_fingerprint(plan),
        structure_fingerprint(a_structure),
        structure_fingerprint(b_structure),
        _mesh_key(mesh),
        np.dtype(dtype).str,
        backend,
        block,
        axis,
        tuple(axes),
        batch,
    )


def compile_spgemm(
    plan,
    a_structure: SparseStructure,
    b_structure: SparseStructure,
    mesh: Mesh,
    *,
    dtype=np.float32,
    backend: str | None = None,
    block: int = 1,
    axis: str = "x",
    axes: tuple[str, str] = ("x", "y"),
    c_structure: SparseStructure | None = None,
    batch: int | None = None,
    cache: bool = True,
) -> CompiledSpGEMM:
    """Get (or build) the AOT executor for a plan + structure + mesh + dtype.

    Cache hits return the *same* ``CompiledSpGEMM`` object — same XLA
    executable, zero retracing.  ``batch=n`` compiles the vmapped executor
    for a fixed batch capacity (one more key dimension — callers should
    bucket ``n`` through ``batch_bucket`` so ragged request batches share an
    executable).  ``cache=False`` bypasses the LRU entirely (a fresh trace +
    compile: the rebuild-everything reference path the benchmarks compare
    against).
    """
    if not cache:
        return CompiledSpGEMM(
            plan, a_structure, b_structure, mesh, dtype=dtype, backend=backend,
            block=block, axis=axis, axes=axes, c_structure=c_structure,
            batch=batch,
        )
    key = _cache_key(
        plan, a_structure, b_structure, mesh, dtype, backend, block, axis, axes, batch
    )
    exe = _CACHE.get(key)
    if exe is not None:
        _CACHE.move_to_end(key)
        _STATS["hits"] += 1
        if exe.c_structure is None and c_structure is not None:
            exe.c_structure = c_structure
        return exe
    _STATS["misses"] += 1
    exe = CompiledSpGEMM(
        plan, a_structure, b_structure, mesh, dtype=dtype, backend=backend,
        block=block, axis=axis, axes=axes, c_structure=c_structure, batch=batch,
    )
    _CACHE[key] = exe
    while len(_CACHE) > CACHE_SIZE:
        _CACHE.popitem(last=False)
    return exe


def cache_info() -> dict:
    return {"size": len(_CACHE), "max_size": CACHE_SIZE, **_STATS}


def cache_clear() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0
