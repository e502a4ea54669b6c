"""Pallas TPU kernel: block-sparse x block-sparse SpGEMM (BSR x BSR -> BSR).

The paper's numeric SpGEMM, TPU-adapted: tiling A, B, C into b x b blocks is
a vertex coarsening of the fine-grained hypergraph (DESIGN.md Sec. 3).  The
host-side inspector enumerates the coarse multiplication vertices — every
(A-block, B-block) pair with matching inner block index — sorted by their
C block (the monochrome-C fiber), and the kernel streams the pair list
through the MXU, accumulating runs of pairs into one C tile.

Grid: (n_pairs,) per call.  Scalar-prefetched pair lists drive the
BlockSpec index maps; the output tile is revisited for consecutive pairs
with equal pair_c and loaded from the running C on its first visit
(sequential TPU grid).  The pair lists live in SMEM (1 MiB on v5e), so a
list longer than ``PAIRS_PER_CALL`` runs as a loop of calls, each adding
its chunk into the C the previous one returned (aliased in place).
VMEM per step: 4 * b^2 * 4B (fp32) -> b=256 still only 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: pairs per pallas_call: three int32 lists of this length take 384 KiB of
#: SMEM (Mosaic refuses ~87K pairs in one call on v5e)
PAIRS_PER_CALL = 32768


def _kernel(n_ref, pa_ref, pb_ref, pc_ref, a_ref, b_ref, c_ref, o_ref, *, acc_dtype):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0, pc_ref[jnp.maximum(i - 1, 0)] != pc_ref[i])

    @pl.when(first)
    def _load():
        o_ref[...] = c_ref[...]

    @pl.when(i < n_ref[0])  # steps past n_ref[0] pad the last chunk
    def _accumulate():
        prod = jnp.dot(
            a_ref[0].astype(acc_dtype),
            b_ref[0].astype(acc_dtype),
            precision=jax.lax.Precision.HIGHEST,  # fp32 products on the MXU
            preferred_element_type=acc_dtype,
        )
        o_ref[...] += prod.astype(o_ref.dtype)


def _accumulate_chunk(c, a_blocks, b_blocks, n_valid, pa, pb, pc, interpret, acc_dtype):
    """One pallas_call: ``c`` plus the products of one chunk of pairs."""
    bm, bk = a_blocks.shape[1], a_blocks.shape[2]
    bn = b_blocks.shape[2]
    return pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # n_valid, pair_a, pair_b, pair_c
            grid=(pa.shape[0],),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda i, n, pa, pb, pc: (pa[i], 0, 0)),
                pl.BlockSpec((1, bk, bn), lambda i, n, pa, pb, pc: (pb[i], 0, 0)),
                pl.BlockSpec((1, bm, bn), lambda i, n, pa, pb, pc: (pc[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda i, n, pa, pb, pc: (pc[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        input_output_aliases={6: 0},  # c (after the 4 prefetch lists, A, B)
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(n_valid, pa, pb, pc, a_blocks, b_blocks, c)


@functools.partial(
    jax.jit, static_argnames=("n_c_blocks", "interpret", "acc_dtype")
)
def _bsr_spgemm_jit(
    a_blocks: jnp.ndarray,
    b_blocks: jnp.ndarray,
    pair_a: jnp.ndarray,
    pair_b: jnp.ndarray,
    pair_c: jnp.ndarray,
    n_c_blocks: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jnp.ndarray:
    n_pairs = pair_a.shape[0]
    out_dtype = jnp.promote_types(a_blocks.dtype, b_blocks.dtype)
    c = jnp.zeros((n_c_blocks, a_blocks.shape[1], b_blocks.shape[2]), out_dtype)
    if n_pairs == 0:
        return c
    chunk = min(n_pairs, PAIRS_PER_CALL)
    n_chunks = -(-n_pairs // chunk)
    pad = n_chunks * chunk - n_pairs

    def chunked(x):
        # padding repeats the last pair: the tail steps stay on the final C
        # tile and skip their product (n_valid)
        x = jnp.concatenate([x, jnp.broadcast_to(x[-1], (pad,))])
        return x.reshape(n_chunks, chunk)

    pa, pb, pc = chunked(pair_a), chunked(pair_b), chunked(pair_c)

    def body(k, c):
        n_valid = jnp.minimum(chunk, n_pairs - k * chunk).astype(jnp.int32)
        return _accumulate_chunk(
            c, a_blocks, b_blocks, n_valid.reshape(1), pa[k], pb[k], pc[k],
            interpret, acc_dtype,
        )

    return jax.lax.fori_loop(0, n_chunks, body, c)


def _pair_list_int32(x) -> jnp.ndarray:
    """Cast one pair-list operand to int32, exactly once, host-side when
    possible: the inspector emits int64, and casting inside jit meant every
    invocation traced/ran an extra convert_element_type on the
    scalar-prefetch path.  Host operands (ndarray / list / tuple) are cast
    in numpy; traced operands (the shard_map executor path) pass through
    unchanged when already int32 and get a single astype otherwise."""
    if isinstance(x, (np.ndarray, list, tuple)):
        return jnp.asarray(np.asarray(x, dtype=np.int32))
    return x if x.dtype == jnp.int32 else x.astype(jnp.int32)


def bsr_spgemm(
    a_blocks: jnp.ndarray,  # (na, bm, bk)
    b_blocks: jnp.ndarray,  # (nb, bk, bn)
    pair_a: jnp.ndarray,  # (np,) int, index into a_blocks
    pair_b: jnp.ndarray,  # (np,) int
    pair_c: jnp.ndarray,  # (np,) int sorted ascending (runs per C block)
    n_c_blocks: int,
    interpret: bool | None = None,
    acc_dtype=jnp.float32,
) -> jnp.ndarray:
    """C blocks ``(n_c_blocks, bm, bn)``: ``C[pair_c] += A[pair_a] @ B[pair_b]``;
    C blocks no pair reaches are zero.  ``interpret=None`` picks the mode
    from the platform (``repro.kernels.resolve_interpret``)."""
    pair_a = _pair_list_int32(pair_a)
    pair_b = _pair_list_int32(pair_b)
    pair_c = _pair_list_int32(pair_c)
    return _bsr_spgemm_jit(
        a_blocks,
        b_blocks,
        pair_a,
        pair_b,
        pair_c,
        n_c_blocks,
        interpret=resolve_interpret(interpret),
        acc_dtype=acc_dtype,
    )


def build_pair_lists(
    a_brows: np.ndarray,
    a_bcols: np.ndarray,
    b_brows: np.ndarray,
    b_bcols: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host-side inspector: coarse multiplication vertices of the tiled
    SpGEMM.  Returns (pair_a, pair_b, pair_c, c_brows, c_bcols) with pair_c
    sorted and C blocks deduplicated.

    Vectorized (CSR-style index arithmetic: group B entries by block-row,
    expand each A entry by its match count, one lexsort); byte-identical to
    ``build_pair_lists_loop``, the original executable specification.
    """
    a_brows = np.asarray(a_brows, dtype=np.int64)
    a_bcols = np.asarray(a_bcols, dtype=np.int64)
    b_brows = np.asarray(b_brows, dtype=np.int64)
    b_bcols = np.asarray(b_bcols, dtype=np.int64)
    z = np.zeros(0, dtype=np.int64)
    if len(a_brows) == 0 or len(b_brows) == 0:
        return z, z, z, z, z
    K = int(max(a_bcols.max(), b_brows.max())) + 1
    # B entries grouped by inner block index k
    b_order = np.argsort(b_brows, kind="stable")
    b_cnt = np.bincount(b_brows, minlength=K)
    b_start = np.cumsum(b_cnt) - b_cnt
    # each A entry i matches the b_cnt[a_bcols[i]] B entries of its k-group
    rep = b_cnt[a_bcols]
    total = int(rep.sum())
    if total == 0:
        return z, z, z, z, z
    ai = np.repeat(np.arange(len(a_brows), dtype=np.int64), rep)
    off = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(rep) - rep, rep)
    bj = b_order[b_start[a_bcols[ai]] + off]
    r, c = a_brows[ai], b_bcols[bj]
    order = np.lexsort((bj, ai, c, r))  # the loop version's (r, c, i, j) sort
    pair_a, pair_b, r, c = ai[order], bj[order], r[order], c[order]
    GC = int(b_bcols.max()) + 1
    uniq, pair_c = np.unique(r * GC + c, return_inverse=True)
    return (
        pair_a,
        pair_b,
        pair_c.astype(np.int64),
        uniq // GC,
        uniq % GC,
    )


def build_pair_lists_loop(
    a_brows: np.ndarray,
    a_bcols: np.ndarray,
    b_brows: np.ndarray,
    b_bcols: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Original pure-Python inspector, kept as the executable specification
    of ``build_pair_lists`` (invariant-tested to match byte for byte)."""
    pairs = []
    by_k: dict[int, list[int]] = {}
    for j, k in enumerate(b_brows):
        by_k.setdefault(int(k), []).append(j)
    for i, (r, k) in enumerate(zip(a_brows, a_bcols)):
        for j in by_k.get(int(k), []):
            pairs.append((int(r), int(b_bcols[j]), i, j))
    if not pairs:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, z, z
    pairs.sort()
    c_coords = sorted({(r, c) for r, c, _, _ in pairs})
    c_id = {rc: n for n, rc in enumerate(c_coords)}
    pair_a = np.array([p[2] for p in pairs], dtype=np.int64)
    pair_b = np.array([p[3] for p in pairs], dtype=np.int64)
    pair_c = np.array([c_id[(p[0], p[1])] for p in pairs], dtype=np.int64)
    c_brows = np.array([rc[0] for rc in c_coords], dtype=np.int64)
    c_bcols = np.array([rc[1] for rc in c_coords], dtype=np.int64)
    return pair_a, pair_b, pair_c, c_brows, c_bcols


def bsr_spgemm_local(
    a_blocks: jnp.ndarray,
    b_blocks: jnp.ndarray,
    pair_a: jnp.ndarray,
    pair_b: jnp.ndarray,
    pair_c: jnp.ndarray,
    n_c_blocks: int,
    backend: str | None = None,
) -> jnp.ndarray:
    """Local-compute entry point the distributed executors route through.

    ``backend``: 'pallas' (the compiled Mosaic kernel), 'interpret' (the
    same kernel through the Pallas interpreter; refused on a TPU), or 'xla'
    (the gather/einsum/segment-add reference, ``ref.bsr_spgemm_ref``).
    ``None`` picks the kernel for the platform: 'pallas' on TPU,
    'interpret' on CPU.
    """
    if backend == "xla":
        from repro.kernels.ref import bsr_spgemm_ref

        return bsr_spgemm_ref(a_blocks, b_blocks, pair_a, pair_b, pair_c, n_c_blocks)
    if backend not in (None, "pallas", "interpret"):
        raise ValueError(f"unknown SpGEMM backend {backend!r}")
    return bsr_spgemm(
        a_blocks,
        b_blocks,
        pair_a,
        pair_b,
        pair_c,
        n_c_blocks=n_c_blocks,
        interpret=None if backend is None else backend == "interpret",
    )
