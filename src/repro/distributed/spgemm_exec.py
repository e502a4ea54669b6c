"""Executor phase: shard_map SpGEMM algorithms.

These realize the paper's algorithm classes as compiled JAX programs:

- ``rowwise_spgemm``: 1D row-wise (Ex. 5.1) with a sparsity-dependent expand
  phase — one padded ``all_to_all`` whose payload is exactly the cut B-nets
  of the partition (plus padding), per ``RowwisePlan``.
- ``monoC_spgemm``: 2D sparsity-dependent monochrome-C (Ex. 5.4) — every
  C (block-)nonzero lives on one device; the cut A-nets and B-nets lower to
  two padded ``all_to_all`` expand phases on a 2D mesh, and local compute
  streams the plan's pair lists through the BSR Pallas kernel
  (``bsr_spgemm_local``, interpret-mode fallback on CPU) so the executor's
  arithmetic is exactly the coarsened multiplication vertices the model
  counts.
- ``fine_spgemm``: 3D fine-grained (Def. 3.1) — an arbitrary flop-level
  partition drives an expand-expand-reduce schedule: two padded
  ``all_to_all`` phases ship the cut A- and B-nets, each device evaluates
  exactly its multiplication vertices and sums them per produced C
  nonzero, and a third ``all_to_all`` (the cut C-nets) folds foreign
  partials into each C nonzero's owner.  Every word any phase moves is one
  (cut net, part) pair of the partition — the connectivity metric made
  executable.  The columnwise and outer models and monoA/monoB run this
  executor too, as fine plans with their multiplications on the part of
  their coarse vertex (``registry._lower_columnwise`` and its siblings).

The sparsity-independent baseline, Sparse SUMMA, lives in ``summa.py``.

Every sparsity-dependent executor consumes an ``ExecutionPlan``
(``plan_ir``): ownership maps + padded routing tables + local work lists.

The fine and monoC steps name their phases with ``jax.named_scope``
(``repro.scatter_values``, ``repro.expand_a``, ``repro.expand_b``,
``repro.local``, ``repro.reduce_c``), so a profiler trace splits the
step's device time by phase; ``owned_c_values`` marks its host work with
``TraceAnnotation`` spans.

Structure-time vs value-time split (DESIGN.md §8): each executor's math
lives in a ``make_*_step`` builder that returns a jit-compatible function
over device-major *packed* operand arrays together with the plan's routing
tables and work lists, which the function takes as arguments (the runtime
uploads them to the devices once; baked in as constants they made compile
time grow with the plan).  The dense entry points
below are thin wrappers over ``repro.distributed.runtime.compile_spgemm``,
which reads nonzero value vectors into the packed layout *inside* the
compiled program and AOT-compiles the whole executor once per
(plan, structure, mesh, dtype, backend) — repeated same-structure calls pay
no host packing, no route re-upload and no retracing.  Correctness oracle:
plain ``A @ B``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map

from repro.distributed.plan_ir import SCALAR_BLOCKS, FinePlan, MonoCPlan, RowwisePlan


def _take0(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather leading-axis slices with -1 padding -> zero slices."""
    safe = jnp.maximum(idx, 0)
    rows = x[safe]
    mask = (idx >= 0).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(mask, rows, 0)


def _take_items(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather last-axis columns of an items-major (items, N) table with -1
    padding -> zero columns."""
    return jnp.where(idx >= 0, x[:, jnp.maximum(idx, 0)], 0)


def _segment_heads(vals: jnp.ndarray, seg: jnp.ndarray, n_pass: int) -> jnp.ndarray:
    """Sum each run of equal ``seg`` entries along ``vals``'s last axis into
    the run's first position: a segmented scan from the right whose pass t
    adds the value ``d = 2**t`` places ahead where it lies in the same run.
    ``n_pass`` passes sum runs of up to ``2**n_pass``; ``seg`` (M,) must
    hold each run contiguously.

    Each pass reads ahead through an offset slice.  Reading behind would
    take a prefix slice, which the TPU compiler turns into a view of the
    very buffer the pass then overwrites in place; on a v5e the blocked
    step summed wrong that way."""
    for t in range(n_pass):
        d = 1 << t
        same = jnp.pad(seg[:-d] == seg[d:], (0, d))
        ahead = jnp.pad(vals[..., d:], [(0, 0)] * (vals.ndim - 1) + [(0, d)])
        vals = vals + jnp.where(same, ahead, 0)
    return vals


def _own_tables(a_blk: jnp.ndarray, b_blk: jnp.ndarray):
    """This device's owned value tables out of its (1, N_max, ...) blocks.

    The runner's gather of the owned values (``owned_values``) fuses into
    this squeeze, and a fused operation takes its scope from its last
    step, so the squeeze carries the gather's scope."""
    with jax.named_scope("repro.scatter_values"):
        return a_blk[0], b_blk[0]


def owned_values(plan, n_a: int, n_b: int, mesh: Mesh, axis, *, columns: bool = False):
    """How the device-major owned tables are built from the caller's value
    vectors, decided from the plan's id lists ``local_ids["a_nz"]`` and
    ``["b_nz"]``, for A and B separately.

    Returns ``(ways, tables, own)``.  An operand whose id list is the
    caller's own order, one device owning ids 0..n-1 in turn (every plan
    built at p=1), takes its vector as the table, with a leading device
    axis: way ``"in_place"``, no data movement and no table.  Any other
    reads the vector through its id list: each device gathers its own row
    of ids, ``N_max`` of them (0 where an id is -1), out of the replicated
    vector under the device scope ``repro.scatter_values``: way
    ``"gathered"``, and the id list is its table.  ``own(a, b, *tables)``
    gives ``(a_own, b_own, rest)``, ``rest`` being what follows the id
    lists in ``tables``.  ``columns``: the vectors are items-major
    (items, n) and the ids name columns.
    """
    if n_a != len(plan.a_part) or n_b != len(plan.b_part):
        raise ValueError("plan was built for a different nonzero structure")
    take = _take_items if columns else _take0

    def gather(values, ids):
        with jax.named_scope("repro.scatter_values"):
            return take(values, ids[0])[None]

    gathered = shard_map(gather, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis))
    ways, tables = [], []
    for ids, n in ((plan.local_ids["a_nz"], n_a), (plan.local_ids["b_nz"], n_b)):
        in_place = ids.shape == (1, n) and np.array_equal(ids[0], np.arange(n))
        ways.append("in_place" if in_place else "gathered")
        tables += [] if in_place else [ids]

    def own(a, b, *tables):
        out = []
        for way, values in zip(ways, (a, b)):
            if way == "in_place":
                out.append(values[None])
            else:
                out.append(gathered(values, tables[0]))
                tables = tables[1:]
        return (*out, tables)

    return tuple(ways), tuple(tables), own


# ---------------------------------------------------------------------------
# 1D row-wise (Ex. 5.1)
# ---------------------------------------------------------------------------
def make_rowwise_step(plan: RowwisePlan, mesh: Mesh, K: int, J: int, axis: str = "x"):
    """Jit-compatible row-wise executor core.

    Returns ``(fn, tables)``: ``fn(a_local, b_local, *tables) -> c_local``
    over device-major packed row tables (``a_local``: (p, I_max, K);
    ``b_local``: (p, K_max, J)); ``tables`` are the plan's route tables
    (send_idx, recv_key: (p, p, T); local_b_rows: (p, K_max)).
    """
    tables = (plan.send_idx, plan.recv_key, plan.local_b_rows)

    def step(a_blk, b_blk, send_idx_blk, recv_key_all, my_b_rows):
        # a_blk: (1, I_max, K); b_blk: (1, K_max, J) — this device's shard
        a_blk = a_blk[0]
        b_blk = b_blk[0]
        send_idx_blk = send_idx_blk[0]  # (p, T) rows I must ship to each dest
        # build the send buffer: (p, T, J)
        send_buf = jax.vmap(lambda idx: _take0(b_blk, idx))(send_idx_blk)
        # expand phase: single all_to_all — THE cut-B-net traffic
        recv_buf = jax.lax.all_to_all(
            send_buf[None], axis, split_axis=1, concat_axis=1, tiled=False
        )[0]
        # recv_buf: (p, T, J) — from each source. Scatter into K-slot table.
        me = jax.lax.axis_index(axis)
        keys = recv_key_all[:, me]  # (p, T) global B-row ids arriving here
        table = jnp.zeros((K, J), b_blk.dtype)
        flat_keys = keys.reshape(-1)
        flat_rows = recv_buf.reshape(-1, J)
        ok = flat_keys >= 0
        table = table.at[jnp.where(ok, flat_keys, K - 1)].add(
            jnp.where(ok[:, None], flat_rows, 0)
        )
        # plus the rows I already own
        my_rows = _take0(b_blk, jnp.arange(b_blk.shape[0]))
        okb = my_b_rows[0] >= 0
        table = table.at[jnp.where(okb, my_b_rows[0], K - 1)].add(
            jnp.where(okb[:, None], my_rows, 0)
        )
        # local compute: my C rows
        return (a_blk @ table)[None]

    shard = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(axis)),
        out_specs=P(axis),
    )
    return shard, tables


def rowwise_spgemm(
    a_dense: np.ndarray,
    b_dense: np.ndarray,
    plan: RowwisePlan,
    mesh: Mesh,
    axis: str = "x",
) -> jnp.ndarray:
    """Sparsity-dependent 1D row-wise SpGEMM.  Returns C rows in plan order
    (device-major: C[d, r] = row ``plan.local_rows[d, r]``).

    Thin wrapper over the compile-once runtime: repeated calls with the same
    sparsity structure hit the cached AOT executable.
    """
    from repro.distributed.runtime import compile_spgemm
    from repro.sparse.structure import from_dense

    a_dense = np.asarray(a_dense)
    b_dense = np.asarray(b_dense)
    a_s, b_s = from_dense(a_dense), from_dense(b_dense)
    exe = compile_spgemm(
        plan,
        a_s,
        b_s,
        mesh,
        dtype=np.promote_types(a_dense.dtype, b_dense.dtype),
        axis=axis,
    )
    ar, ac = a_s.coo()
    br, bc = b_s.coo()
    return exe(a_dense[ar, ac], b_dense[br, bc])


def unpack_rowwise_result(c_local: jnp.ndarray, plan: RowwisePlan, I: int) -> np.ndarray:
    c_np = np.asarray(c_local)
    out = np.zeros((I, c_np.shape[-1]), dtype=c_np.dtype)
    dev, slot = np.nonzero(plan.local_rows >= 0)
    out[plan.local_rows[dev, slot]] = c_np[dev, slot]
    return out


# ---------------------------------------------------------------------------
# 2D monochrome-C (Ex. 5.4)
# ---------------------------------------------------------------------------
def make_monoC_step(
    plan: MonoCPlan,
    mesh: Mesh,
    block: int = 8,
    backend: str | None = None,
    axes: tuple[str, str] = ("x", "y"),
):
    """Jit-compatible monochrome-C executor core.

    Returns ``(fn, tables)``: ``fn(a_own, b_own, *tables) -> c_local`` over
    device-major packed block tables ((p, N_max, b, b)); ``tables`` are the
    two expand routes' send slots and the BSR pair lists.
    """
    from repro.kernels.bsr_spgemm import bsr_spgemm_local

    p = plan.p
    route_a, route_b = plan.routes["expand_a"], plan.routes["expand_b"]
    T_a, T_b = route_a.T, route_b.T
    n_c_slots = plan.n_c_slots
    tables = (
        route_a.send_idx,
        route_b.send_idx,
        plan.compute["pair_a"],
        plan.compute["pair_b"],
        plan.compute["pair_c"],
    )

    def expand(own, send_idx_blk, T):
        # own: (N_max, b, b); send_idx_blk: (p, T) local slots to ship
        buf = _take0(own, send_idx_blk.reshape(-1)).reshape(p, T, block, block)
        # THE cut-net traffic of this operand: one all_to_all over the
        # flattened 2D mesh
        recv = jax.lax.all_to_all(
            buf[None], axes, split_axis=1, concat_axis=1, tiled=False
        )[0]
        zero = jnp.zeros((1, block, block), own.dtype)
        return jnp.concatenate([own, recv.reshape(p * T, block, block), zero], 0)

    def step(a_blk, b_blk, sa_, sb_, pa_, pb_, pc_):
        a_own, b_own = _own_tables(a_blk, b_blk)
        with jax.named_scope("repro.expand_a"):
            a_tab = expand(a_own, sa_[0], T_a)
        with jax.named_scope("repro.expand_b"):
            b_tab = expand(b_own, sb_[0], T_b)
        with jax.named_scope("repro.local"):
            c = bsr_spgemm_local(
                a_tab, b_tab, pa_[0], pb_[0], pc_[0], n_c_blocks=n_c_slots, backend=backend
            )
        return c[None]

    spec = P(axes)
    shard = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec,) * 7,
        out_specs=spec,
    )
    return shard, tables


def monoC_spgemm(
    a_dense: np.ndarray,
    b_dense: np.ndarray,
    plan: MonoCPlan,
    mesh: Mesh,
    axes: tuple[str, str] = ("x", "y"),
    block: int = 8,
    backend: str | None = None,
) -> jnp.ndarray:
    """2D sparsity-dependent monochrome-C SpGEMM (Ex. 5.4).

    ``plan`` must have been built on the b x b block structures of the
    operands (``plan_ir.plan_monoC_from_dense`` does both steps): C block
    (i, j) lives on one device; two padded ``all_to_all`` phases over the
    flattened 2D mesh ship exactly the cut A-nets and B-nets, after which
    each device streams its pair list through the BSR kernel path
    (``bsr_spgemm_local`` — Pallas on TPU, interpret-mode fallback on CPU,
    optional XLA dense fallback) over slot tables laid out as
    ``[owned | received | zero]``.

    Returns device-major C block shards (p, C_max + 1, b, b); the trailing
    slot per device is the padding sink.  Use ``unpack_monoC_result``.  Thin
    wrapper over the compile-once runtime: the tiling here is the only
    per-call structure work, and same-structure calls hit the cached AOT
    executable.
    """
    from repro.distributed.runtime import compile_spgemm
    from repro.sparse.bsr import to_bsr

    ab = to_bsr(np.asarray(a_dense), block, block)
    bb = to_bsr(np.asarray(b_dense), block, block)
    if len(plan.a_part) != ab.n_blocks or len(plan.b_part) != bb.n_blocks:
        raise ValueError("plan was built for a different block structure")
    exe = compile_spgemm(
        plan,
        ab.block_structure(),
        bb.block_structure(),
        mesh,
        dtype=np.promote_types(ab.blocks.dtype, bb.blocks.dtype),
        backend=backend,
        block=block,
        axes=axes,
    )
    return exe(ab.blocks, bb.blocks)


def owned_c_values(c_local: jnp.ndarray, plan) -> np.ndarray:
    """Device-major owned-C slots -> C values in canonical CSR order.

    Works for every plan whose C lives in owned slots (fine, monoA, monoB,
    monoC, summa2d): ``plan.local_ids["c_nz"]`` names the C nonzero each
    slot holds.  The result is ``(nnz(C),)`` for scalar plans and
    ``(nnz(C), b, b)`` for blocked ones (monoC's tiles), ``(nnz(C), r, c)``
    for a fine-family plan over block operands, whose step returns each
    device's slots flat, block after block; nothing is densified.  The
    slot -> canonical map is built on a plan's first unpack and memoized on
    the plan (``_c_order``); each product then pays one gather.  The host
    spans are ``repro.unpack.fetch`` (device-to-host copy),
    ``repro.unpack.order_map`` (the one-time build) and
    ``repro.unpack.reorder`` (the per-product gather).
    """
    with TraceAnnotation("repro.unpack.fetch"):
        c_np = np.asarray(c_local)
    if plan.blocks != SCALAR_BLOCKS:
        (r, _), (_, c) = plan.blocks
        c_np = c_np.reshape(plan.p, -1, r, c)
    idx = _c_order(plan, c_np.shape[1])
    with TraceAnnotation("repro.unpack.reorder"):
        return _canonical_order(c_np, idx)


def _canonical_order(c_np: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Gather ``c_np``'s (p, n_slots, ...) slots into canonical C order
    through the flat slot index ``idx``.  ``np.take`` gives a fresh array
    that owns its data, never a view of the fetched buffer."""
    p, n_slots = c_np.shape[:2]
    return np.take(c_np.reshape(p * n_slots, *c_np.shape[2:]), idx, axis=0)


def _c_order(plan, n_slots: int) -> np.ndarray:
    """Flat slot ``dev * n_slots + slot`` of every C nonzero, in canonical
    order, as an intp index (``np.take`` converts an int32 one on every
    call).  Built once and memoized on the plan as
    ``plan._c_order = (n_slots, index)``, like ``runtime.plan_fingerprint``;
    the plan store and the fingerprint read neither."""
    memo = getattr(plan, "_c_order", None)
    if memo is not None and memo[0] == n_slots:
        return memo[1]
    with TraceAnnotation("repro.unpack.order_map"):
        local_c = plan.local_ids["c_nz"]
        nnz_c = len(plan.ownership["c_nz"])
        dev, slot = np.nonzero(local_c >= 0)
        ids = local_c[dev, slot]
        covered = np.zeros(nnz_c, bool)
        fits = local_c.shape[1] <= n_slots and ids.max(initial=-1) < nnz_c
        if fits and len(ids) == nnz_c:
            covered[ids] = True
        if not covered.all():
            raise ValueError(
                f"plan's C slots do not hold each of the {nnz_c} C nonzeros "
                f"exactly once in a table of {n_slots} slots"
            )
        idx = np.empty(nnz_c, np.intp)
        idx[ids] = dev * n_slots + slot
        plan._c_order = (n_slots, idx)
    return idx


def unpack_monoC_result(
    c_local: jnp.ndarray,
    plan: MonoCPlan,
    c_structure,
    shape: tuple[int, int],
) -> np.ndarray:
    """Scatter device-major C block slots back to a dense array.

    ``c_structure`` is the block-grid structure of C (``inst.c`` of the plan
    instance); ``shape`` the padded dense shape (block-grid * block).
    """
    return _dense_from_blocks(owned_c_values(c_local, plan), c_structure, shape)


def _dense_from_blocks(vals: np.ndarray, c_structure, shape) -> np.ndarray:
    """(nnz(C), r, c) block values on C's block structure -> dense ``shape``."""
    r, c = vals.shape[1:]
    crow, ccol = c_structure.coo()
    out = np.zeros((shape[0] // r, shape[1] // c, r, c), dtype=vals.dtype)
    out[crow, ccol] = vals
    return out.transpose(0, 2, 1, 3).reshape(shape)


# ---------------------------------------------------------------------------
# 3D fine-grained (Def. 3.1)
# ---------------------------------------------------------------------------
def _fine_tables(plan: FinePlan) -> tuple:
    """The fine step's table arguments: the expand routes' send slots, the
    multiplication lists, the reduce's arrival slots and two maps into the
    segmented sum of the products (``_segment_heads``): ``send_head``
    (p, p, T_r), the position of each reduce item's sum (the route's send
    slots composed with ``plan.prod_heads``), and ``own_head``
    (p, C_max + 1), the position of the sum each owned C slot takes
    (``prod_to_owned`` inverted and composed the same way; -1 where the
    device produces nothing for the slot, and in the trailing garbage
    slot)."""
    p = plan.p
    heads = plan.prod_heads
    send = plan.routes["reduce_c"].send_idx
    dev = np.arange(p)[:, None, None]
    send_head = np.where(send >= 0, heads[dev, np.maximum(send, 0)], -1)
    prod_own = plan.compute["prod_to_owned"]
    own_head = np.full((p, plan.n_c_slots), -1, dtype=np.int64)
    d, s = np.nonzero(prod_own >= 0)
    own_head[d, prod_own[d, s]] = heads[d, s]
    return (
        plan.routes["expand_a"].send_idx,
        plan.routes["expand_b"].send_idx,
        send_head,
        plan.compute["pair_a"],
        plan.compute["pair_b"],
        plan.compute["pair_c"],
        plan.compute["reduce_recv_slot"],
        own_head,
    )


def make_fine_step(plan: FinePlan, mesh: Mesh, axis: str = "x"):
    """Jit-compatible fine-grained executor core (expand-expand-reduce).

    Returns ``(fn, tables)``: ``fn(a_own, b_own, *tables) -> c_local`` over
    device-major packed scalar slot tables ((p, N_max)); ``tables`` are
    ``_fine_tables``'s.  Local compute sums each device's products per
    produced slot by a segmented scan (``plan.segment_passes`` passes), and
    the owned C table and the reduce's send buffer gather the run heads.  A
    plan over block operands (``plan.blocks`` = ((r, k), (k, c))) gets
    ``_make_blocked_fine_step``'s items-major step over the same tables.
    """
    if plan.blocks != SCALAR_BLOCKS:
        return _make_blocked_fine_step(plan, mesh, axis)
    p = plan.p
    route_a = plan.routes["expand_a"]
    route_b = plan.routes["expand_b"]
    route_r = plan.routes["reduce_c"]
    T_a, T_b, T_r = route_a.T, route_b.T, route_r.T
    C_max = plan.local_ids["c_nz"].shape[1]
    n_pass = plan.segment_passes
    tables = _fine_tables(plan)

    def expand(own, send_idx_blk, T):
        # own: (N_max,); ship my cut-net scalars, receive the foreign ones
        buf = _take0(own, send_idx_blk.reshape(-1)).reshape(p, T)
        recv = jax.lax.all_to_all(
            buf[None], axis, split_axis=1, concat_axis=1, tiled=False
        )[0]
        zero = jnp.zeros((1,), own.dtype)
        return jnp.concatenate([own, recv.reshape(p * T), zero], 0)

    def step(a_blk, b_blk, sa_, sb_, send_head_, pa_, pb_, pc_, recv_slot_all, own_head_):
        a_own, b_own = _own_tables(a_blk, b_blk)
        with jax.named_scope("repro.expand_a"):
            a_tab = expand(a_own, sa_[0], T_a)
        with jax.named_scope("repro.expand_b"):
            b_tab = expand(b_own, sb_[0], T_b)
        with jax.named_scope("repro.local"):
            # exactly this device's multiplication vertices, summed per
            # produced slot at the head of the slot's run
            sums = _segment_heads(a_tab[pa_[0]] * b_tab[pb_[0]], pc_[0], n_pass)
            # partials this device both produced and owns
            c = _take0(sums, own_head_[0])
        with jax.named_scope("repro.reduce_c"):
            # ship foreign partials to their C owners and fold them in
            buf = _take0(sums, send_head_[0].reshape(-1)).reshape(p, T_r)
            recv = jax.lax.all_to_all(
                buf[None], axis, split_axis=1, concat_axis=1, tiled=False
            )[0]
            me = jax.lax.axis_index(axis)
            slots = recv_slot_all[:, me].reshape(-1)  # owned-C slot per arrival
            ok = slots >= 0
            c = c.at[jnp.where(ok, slots, C_max)].add(
                jnp.where(ok, recv.reshape(-1), 0)
            )
        return c[None]

    shard = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis),) * 8 + (P(), P(axis)),
        out_specs=P(axis),
    )
    return shard, tables


def _make_blocked_fine_step(plan: FinePlan, mesh: Mesh, axis: str):
    """The fine step over block operands, items-major.

    Every table holds one row per item of a block and one column per slot:
    A's (r*k, N_max), B's (k*c, N_max), the produced and owned C tables
    (r*c, slots), so the nonzero index sits on the TPU's 128 lanes (an
    (N, 3, 3) table would be padded to (8, 128) tiles in its last two
    dimensions).  The multiplication lists are the scalar plan's, one entry
    per block product: local compute gathers A's r*k and B's k*c items of
    each, forms its r*c outputs as sums over k, sums them per produced
    slot by the segmented scan and gathers the owned C columns from the
    run heads; the routes ship whole blocks (columns)."""
    p = plan.p
    (r, k), (_, c) = plan.blocks
    T_a, T_b, T_r = (plan.routes[n].T for n in ("expand_a", "expand_b", "reduce_c"))
    C_max = plan.local_ids["c_nz"].shape[1]
    n_pass = plan.segment_passes
    tables = _fine_tables(plan)

    def exchange(tab, send_idx_blk, T):
        # ship the columns named by send_idx_blk (p, T) to each device; the
        # arrivals come back (items, p * T), source-major
        buf = _take_items(tab, send_idx_blk.reshape(-1)).reshape(-1, p, T)
        recv = jax.lax.all_to_all(
            buf[None], axis, split_axis=2, concat_axis=2, tiled=False
        )[0]
        return recv.reshape(-1, p * T)

    def expand(own, send_idx_blk, T):
        zero = jnp.zeros((own.shape[0], 1), own.dtype)
        return jnp.concatenate([own, exchange(own, send_idx_blk, T), zero], 1)

    def products(a_tab, b_tab, pa, pb):
        # one gather of each item row, then c_ij = sum_q a_iq * b_qj for
        # every multiplication at once: (r*c, M)
        a_g = a_tab[:, pa]
        b_g = b_tab[:, pb]
        rows = []
        for i in range(r):
            for j in range(c):
                acc = a_g[i * k] * b_g[j]
                for q in range(1, k):
                    acc = acc + a_g[i * k + q] * b_g[q * c + j]
                rows.append(acc)
        return jnp.stack(rows)

    def step(a_blk, b_blk, sa_, sb_, send_head_, pa_, pb_, pc_, recv_slot_all, own_head_):
        a_own, b_own = _own_tables(a_blk, b_blk)
        with jax.named_scope("repro.expand_a"):
            a_tab = expand(a_own, sa_[0], T_a)
        with jax.named_scope("repro.expand_b"):
            b_tab = expand(b_own, sb_[0], T_b)
        with jax.named_scope("repro.local"):
            sums = _segment_heads(products(a_tab, b_tab, pa_[0], pb_[0]), pc_[0], n_pass)
            c_tab = _take_items(sums, own_head_[0])
        with jax.named_scope("repro.reduce_c"):
            recv = exchange(sums, send_head_[0], T_r)
            me = jax.lax.axis_index(axis)
            slots = recv_slot_all[:, me].reshape(-1)
            ok = slots >= 0
            c_tab = c_tab.at[:, jnp.where(ok, slots, C_max)].add(jnp.where(ok, recv, 0))
        return c_tab[None]

    shard = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis),) * 8 + (P(), P(axis)),
        out_specs=P(axis),
    )
    return shard, tables


def fine_spgemm(
    a,
    b,
    plan: FinePlan,
    mesh: Mesh,
    axis: str = "x",
) -> jnp.ndarray:
    """3D fine-grained SpGEMM (Def. 3.1): expand-expand-reduce.

    ``plan`` is a ``FinePlan`` over the scalar nonzero structures of the
    operands (``plan_ir.plan_fine_from_dense`` builds both).  Three padded
    ``all_to_all`` phases over the 1D device axis realize the three cut-net
    families of the fine hypergraph partition:

    1. A-expand: each device receives the foreign A nonzeros its
       multiplications read (slot table ``[owned | received | zero]``);
    2. B-expand: same for B;
    3. local compute: the device's multiplication list is two gathers, an
       elementwise product, and a segmented sum over its runs of one
       produced slot — exactly its multiplication vertices, no more;
    4. C-reduce: foreign partials ship to each C nonzero's owner and fold
       into the owned-C table; partials the producer already owns are
       gathered from their run heads (``prod_to_owned`` composed with
       ``plan.prod_heads``).

    ``a`` / ``b`` may each be a dense array, a scipy sparse matrix, or an
    ``(SparseStructure, values)`` pair — callers that already hold sparse
    operands never densify.  Returns device-major owned-C slot values
    (p, C_max + 1); the trailing slot per device is the padding sink.  Use
    ``unpack_fine_result``.  Thin wrapper over the compile-once runtime.
    """
    from repro.distributed.runtime import compile_spgemm
    from repro.sparse.structure import structure_and_values

    a_s, a_vals = structure_and_values(a)
    b_s, b_vals = structure_and_values(b)
    if a_s.nnz != len(plan.a_part) or b_s.nnz != len(plan.b_part):
        raise ValueError("plan was built for a different nonzero structure")
    exe = compile_spgemm(
        plan,
        a_s,
        b_s,
        mesh,
        dtype=np.promote_types(a_vals.dtype, b_vals.dtype),
        axis=axis,
    )
    return exe(a_vals, b_vals)


def unpack_fine_result(
    c_local: jnp.ndarray,
    plan: FinePlan,
    c_structure,
    shape: tuple[int, int],
) -> np.ndarray:
    """Scatter device-major owned-C slot values back to a dense array."""
    vals = owned_c_values(c_local, plan)
    if vals.ndim == 3:
        return _dense_from_blocks(vals, c_structure, shape)
    crow, ccol = c_structure.coo()
    out = np.zeros(shape, dtype=vals.dtype)
    out[crow, ccol] = vals
    return out
