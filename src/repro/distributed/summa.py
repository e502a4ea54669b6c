"""Sparse SUMMA: the sparsity-*oblivious* 2D baseline the paper beats.

The seven hypergraph models ship exactly the cut-net traffic of a partition
tuned to the instance's sparsity.  The classic competitor — Sparse SUMMA
(Buluc & Gilbert, arXiv 1109.3739 / 1006.2183) — fixes the data
distribution up front and broadcasts whole sparse panels regardless of who
actually needs them:

- devices form a ``(pr, pc)`` grid, flattened row-major
  (``d = r * pc + c`` — the same flattening the monoC executor's
  two-axis ``all_to_all`` uses);
- A, B and C are distributed element-cyclically: ``A(i, k)`` lives on
  ``(i % pr, k % pc)``, ``B(k, j)`` on ``(k % pr, j % pc)``, ``C(i, j)``
  stays put on ``(i % pr, j % pc)`` (stationary C);
- the multiply runs in ``n_stages = lcm(pr, pc)`` pipelined stages: stage
  ``t`` broadcasts every A nonzero with ``k % n_stages == t`` along its
  mesh *row* (``pc - 1`` copies) and every such B nonzero along its mesh
  *column* (``pr - 1`` copies), then each device multiplies the panel pair
  into its owned C slots through the BSR kernel path.

Because the broadcast is oblivious, the analytic communication volume is
closed-form — ``nnz(A) * (pc - 1) + nnz(B) * (pr - 1)`` words — and the
per-stage ``Route`` tables enumerate exactly those transfers, so
``measured_route_words(plan) == summa_words_ideal(...)`` is the same
measured == predicted check the hypergraph models pass, with the
connectivity metric replaced by the closed form.  ``benchmarks/
bench_versus.py`` compares ``model="auto"`` against this baseline on the
application instances — the paper's headline claim as a live gate.

Planning here is pure numpy (jax only enters inside the runner/step
factories), matching the lazy-import contract of the rest of the
planning stack.
"""
from __future__ import annotations

import math

import numpy as np

from repro.core.spgemm_models import SpGEMMInstance
from repro.distributed.plan_ir import (
    ExecutionPlan,
    _table_slots,
    build_route,
    padded_id_lists,
)


class SummaPlan(ExecutionPlan):
    """Stationary-C Sparse SUMMA plan over a ``(pr, pc)`` device grid.

    Routes ``bcast_a_s{t}`` / ``bcast_b_s{t}`` hold the stage-``t`` panel
    broadcasts; ``pair_*_s{t}`` are the stage-``t`` BSR pair lists in the
    monoC slot-table convention (``[owned | received | zero]`` operand
    tables, owned-C slots plus one trailing garbage slot).
    """

    @property
    def pr(self) -> int:
        return int(self.stats["pr"])

    @property
    def pc(self) -> int:
        return int(self.stats["pc"])

    @property
    def n_stages(self) -> int:
        return int(self.stats["n_stages"])

    @property
    def a_part(self) -> np.ndarray:
        return self.ownership["a_nz"]

    @property
    def b_part(self) -> np.ndarray:
        return self.ownership["b_nz"]

    @property
    def c_part(self) -> np.ndarray:
        return self.ownership["c_nz"]

    @property
    def n_c_slots(self) -> int:
        """Local C slots incl. the trailing garbage slot padding pairs hit."""
        return self.local_ids["c_nz"].shape[1] + 1


def summa_words_ideal(
    inst: SpGEMMInstance, pr: int, pc: int, word_size: int = 1
) -> int:
    """Closed-form SUMMA volume: every A nonzero is broadcast to the other
    ``pc - 1`` columns of its mesh row, every B nonzero to the other
    ``pr - 1`` rows of its mesh column — sparsity of the *other* operand
    never enters (that obliviousness is the whole point of the baseline)."""
    return int((inst.a.nnz * (pc - 1) + inst.b.nnz * (pr - 1)) * word_size)


def summa_mesh_shape(p: int, inst: SpGEMMInstance | None = None) -> tuple[int, int]:
    """Pick the ``(pr, pc)`` factorization of ``p`` for an instance.

    With an instance in hand the aspect is chosen to minimize the analytic
    volume ``nnz(A) * (pc - 1) + nnz(B) * (pr - 1)`` (an A-heavy instance
    wants few columns, a B-heavy one few rows); without one, nearest-square.
    Ties break toward square, then toward more rows.
    """
    best = None
    for pr in range(1, p + 1):
        if p % pr:
            continue
        pc = p // pr
        vol = 0 if inst is None else summa_words_ideal(inst, pr, pc)
        key = (vol, abs(pr - pc), pc)
        if best is None or key < best[0]:
            best = (key, (pr, pc))
    return best[1]


def build_summa_plan(
    inst: SpGEMMInstance,
    p: int,
    pr: int | None = None,
    pc: int | None = None,
    word_size: int = 1,
) -> SummaPlan:
    """Lower an instance straight to a Sparse SUMMA plan (no partition).

    ``pr``/``pc`` default to ``summa_mesh_shape(p, inst)``.  The stage count
    is ``lcm(pr, pc)`` so the element-cyclic owner maps stay pure 2D cyclic
    (``t(k) % pc == k % pc`` and ``t(k) % pr == k % pr``).
    """
    if pr is None or pc is None:
        pr, pc = summa_mesh_shape(p, inst)
    if pr * pc != p:
        raise ValueError(f"(pr, pc) = ({pr}, {pc}) does not factor p = {p}")
    S = math.lcm(pr, pc)
    nA, nB, nC = inst.a.nnz, inst.b.nnz, inst.c.nnz
    ar, ak = inst.a.coo()
    bk, bj = inst.b.coo()
    cr, cj = inst.c.coo()

    a_part = (ar % pr) * pc + ak % pc
    b_part = (bk % pr) * pc + bj % pc
    c_part = (cr % pr) * pc + cj % pc
    local_a, local_of_a = padded_id_lists(a_part, p)
    local_b, local_of_b = padded_id_lists(b_part, p)
    local_c, local_of_c = padded_id_lists(c_part, p)
    A_max, B_max, C_max = local_a.shape[1], local_b.shape[1], local_c.shape[1]

    def _broadcast_route(ids, owner_rc, along_cols, payload):
        """Oblivious broadcast of the stage panel: each item goes from its
        owner to the other ``w - 1`` positions of its mesh row (A) or
        column (B).  Item-major by construction (ids ascend)."""
        rr, cc = owner_rc
        w = pc if along_cols else pr
        lane = np.broadcast_to(np.arange(w, dtype=np.int64), (len(ids), w))
        keep = lane != (cc if along_cols else rr)[:, None]
        if along_cols:
            dst = ((rr[:, None] * pc) + lane)[keep]
        else:
            dst = ((lane * pc) + cc[:, None])[keep]
        src = np.repeat(rr * pc + cc, w - 1)
        item = np.repeat(ids, w - 1)
        local_of = local_of_a if payload == "A" else local_of_b
        return build_route(src, dst, item, local_of, p, payload, word_size)

    a_stage = ak % S
    b_stage = bk % S
    mult_stage = inst.mult_k % S
    mult_dev = (inst.mult_i % pr) * pc + inst.mult_j % pc
    a_pos, b_pos, c_pos = inst.mult_a_pos, inst.mult_b_pos, inst.mult_c_pos

    routes, compute = {}, {}
    n_pairs = 0
    for t in range(S):
        ids_a = np.nonzero(a_stage == t)[0]
        route_a = _broadcast_route(ids_a, (ar[ids_a] % pr, ak[ids_a] % pc), True, "A")
        ids_b = np.nonzero(b_stage == t)[0]
        route_b = _broadcast_route(ids_b, (bk[ids_b] % pr, bj[ids_b] % pc), False, "B")
        routes[f"bcast_a_s{t}"] = route_a
        routes[f"bcast_b_s{t}"] = route_b

        # stage-t pair lists: every multiplication whose k falls in this
        # panel runs on the (stationary) owner of its C nonzero, reading the
        # [owned | received | zero] tables the stage broadcasts filled
        a_slots = _table_slots(a_part, local_of_a, route_a, nA, p)
        b_slots = _table_slots(b_part, local_of_b, route_b, nB, p)
        sel = np.nonzero(mult_stage == t)[0]
        dev = mult_dev[sel]
        pa = a_slots[dev, a_pos[sel]]
        pb = b_slots[dev, b_pos[sel]]
        pcs = local_of_c[c_pos[sel]]
        assert (pa >= 0).all() and (pb >= 0).all(), (
            "SUMMA broadcast missed a needed nonzero"
        )
        order = np.lexsort((pb, pa, pcs, dev))
        pa, pb, pcs, dev = pa[order], pb[order], pcs[order], dev[order]
        counts = np.bincount(dev, minlength=p)
        P_max = max(int(counts.max(initial=0)), 1)
        starts = np.cumsum(counts) - counts
        rank = np.arange(len(dev), dtype=np.int64) - np.repeat(starts, counts)
        pair_a = np.full((p, P_max), A_max + p * route_a.T, dtype=np.int64)
        pair_b = np.full((p, P_max), B_max + p * route_b.T, dtype=np.int64)
        pair_c = np.full((p, P_max), C_max, dtype=np.int64)
        pair_a[dev, rank] = pa
        pair_b[dev, rank] = pb
        pair_c[dev, rank] = pcs
        compute[f"pair_a_s{t}"] = pair_a
        compute[f"pair_b_s{t}"] = pair_b
        compute[f"pair_c_s{t}"] = pair_c
        n_pairs += int(len(dev))

    plan = SummaPlan(
        model="summa2d",
        p=p,
        ownership={"a_nz": a_part, "b_nz": b_part, "c_nz": c_part},
        local_ids={"a_nz": local_a, "b_nz": local_b, "c_nz": local_c},
        routes=routes,
        compute=compute,
        stats={
            "pr": int(pr),
            "pc": int(pc),
            "n_stages": int(S),
            "n_pairs": n_pairs,
            "words_analytic": summa_words_ideal(inst, pr, pc, word_size),
        },
    )
    assert plan.comm_words_ideal == plan.stats["words_analytic"], (
        "stage routes diverged from the closed-form SUMMA volume"
    )
    assert n_pairs == inst.n_mult, "stage pair lists dropped a multiplication"
    return plan


def _lower_summa(inst: SpGEMMInstance, parts, p: int) -> SummaPlan:
    """Registry lowerer: SUMMA is partition-free, ``parts`` is ignored
    (``None`` from the front door — there is no hypergraph to partition)."""
    return build_summa_plan(inst, p)


def make_summa_step(
    plan: SummaPlan,
    mesh,
    block: int = 1,
    backend: str | None = None,
    axes: tuple[str, str] = ("x", "y"),
):
    """Jit-compatible SUMMA executor core.

    Returns ``(fn, tables)``: ``fn(a_own, b_own, *tables) -> c_local`` over
    device-major packed block tables ``(p, N_max, b, b)``; ``tables`` holds
    each stage's two send-slot tables and three pair lists.  The stage loop
    is unrolled in Python — ``n_stages`` is a small compile-time constant
    (``lcm(pr, pc)``), so the whole pipeline AOT-compiles to one executable
    and each stage is the monoC expand (gather -> flattened two-axis
    ``all_to_all`` -> concat) followed by a BSR pair-list multiply
    accumulated into the owned C slots.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.distributed.spgemm_exec import _take0
    from repro.kernels.bsr_spgemm import bsr_spgemm_local

    p = plan.p
    S = plan.n_stages
    n_c_slots = plan.n_c_slots
    stage_T = []
    tables = []
    for t in range(S):
        route_a = plan.routes[f"bcast_a_s{t}"]
        route_b = plan.routes[f"bcast_b_s{t}"]
        stage_T.append((route_a.T, route_b.T))
        tables += [
            route_a.send_idx,
            route_b.send_idx,
            plan.compute[f"pair_a_s{t}"],
            plan.compute[f"pair_b_s{t}"],
            plan.compute[f"pair_c_s{t}"],
        ]

    def expand(own, send_idx_blk, T):
        buf = _take0(own, send_idx_blk.reshape(-1)).reshape(p, T, block, block)
        recv = jax.lax.all_to_all(
            buf[None], axes, split_axis=1, concat_axis=1, tiled=False
        )[0]
        zero = jnp.zeros((1, block, block), own.dtype)
        return jnp.concatenate([own, recv.reshape(p * T, block, block), zero], 0)

    def step(a_blk, b_blk, *tabs):
        a_own, b_own = a_blk[0], b_blk[0]
        c = jnp.zeros((n_c_slots, block, block), a_own.dtype)
        for t in range(S):
            sa_, sb_, pa_, pb_, pc_ = tabs[5 * t : 5 * t + 5]
            T_a, T_b = stage_T[t]
            a_tab = expand(a_own, sa_[0], T_a)
            b_tab = expand(b_own, sb_[0], T_b)
            c = c + bsr_spgemm_local(
                a_tab, b_tab, pa_[0], pb_[0], pc_[0],
                n_c_blocks=n_c_slots, backend=backend,
            )
        return c[None]

    spec = P(axes)
    shard = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec,) * (2 + 5 * S),
        out_specs=spec,
    )
    return shard, tuple(tables)


def _summa_runner(plan, a_structure, b_structure, mesh, *, dtype, block, backend, axis, axes):
    """Registry runner factory (monoC value layout: ``(nnz, b, b)`` blocks
    read into device-major owned tables)."""
    from repro.distributed.registry import owned_nz_setup

    step, tables = make_summa_step(plan, mesh, block=block, backend=backend, axes=axes)
    return owned_nz_setup(
        plan,
        a_structure,
        b_structure,
        step,
        tables,
        mesh,
        axes,
        item_shape=(block, block),
        out_shape=(a_structure.shape[0] * block, b_structure.shape[1] * block),
    )
