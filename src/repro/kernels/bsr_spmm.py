"""Pallas TPU kernel: BSR (block-sparse rows) x dense -> dense.

TPU adaptation of the paper's local SpGEMM compute phase: the hypergraph's
multiplication vertices are coarsened to b_m x b_k blocks (DESIGN.md Sec. 3),
each grid step feeds one block product to the MXU.  Block coordinates ride in
SMEM via scalar prefetch; accumulation into a revisited output tile relies on
TPU's sequential grid execution (blocks are pre-sorted by output row, so the
first-visit predicate initializes the tile).

Grid: (n_blocks, N / b_n).  VMEM working set per step:
b_m*b_k (A block) + b_k*b_n (B tile) + b_m*b_n (accumulator) — e.g.
128^2 * 3 * 4B = 196 KiB, comfortably within the ~16 MiB VMEM budget; b_n can
be raised to widen the MXU N dimension once b_k*b_n stays under ~4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(brows_ref, bcols_ref, a_ref, b_ref, o_ref, *, acc_dtype):
    i = pl.program_id(1)  # block index (inner grid axis)
    # first visit of this output row-block: initialize the accumulator tile
    first = jnp.logical_or(i == 0, brows_ref[jnp.maximum(i - 1, 0)] != brows_ref[i])

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    prod = jnp.dot(
        a_ref[0].astype(acc_dtype),
        b_ref[...].astype(acc_dtype),
        preferred_element_type=acc_dtype,
    )
    o_ref[...] += prod.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("m_blocks", "b_n", "interpret", "acc_dtype")
)
def bsr_spmm(
    blocks: jnp.ndarray,  # (nb, b_m, b_k), sorted by brows
    brows: jnp.ndarray,  # (nb,) int32
    bcols: jnp.ndarray,  # (nb,) int32
    dense: jnp.ndarray,  # (K, N)
    m_blocks: int,
    b_n: int = 128,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jnp.ndarray:
    nb, b_m, b_k = blocks.shape
    K, N = dense.shape
    b_n = min(b_n, N)
    if N % b_n:
        raise ValueError(f"N={N} not divisible by b_n={b_n}")
    # grid: j outer, block index inner — same-row runs revisit the output
    # tile on CONSECUTIVE steps (TPU revisiting requirement).  Caller must
    # guarantee every output block-row has at least one (possibly zero)
    # block, else that row's tiles are never initialized (ops.spmm pads).
    grid = (N // b_n, nb)
    out_dtype = jnp.promote_types(blocks.dtype, dense.dtype)
    kernel = functools.partial(_kernel, acc_dtype=acc_dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # brows, bcols
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, b_m, b_k), lambda j, i, brows, bcols: (i, 0, 0)),
                pl.BlockSpec((b_k, b_n), lambda j, i, brows, bcols: (bcols[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (b_m, b_n), lambda j, i, brows, bcols: (brows[i], j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m_blocks * b_m, N), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(brows.astype(jnp.int32), bcols.astype(jnp.int32), blocks, dense)
    return out
