"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) runs the compiled Mosaic kernels on a TPU
and the Pallas interpreter on the CPU (``repro.kernels.resolve_interpret``).
The BSR entry points also accept host-side ``BlockSparse`` matrices and run
the inspector (pair-list construction).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, resolve_interpret
from repro.kernels.bsr_spgemm import bsr_spgemm, build_pair_lists
from repro.kernels.bsr_spmm import bsr_spmm
from repro.kernels.moe_gemm import moe_gemm
from repro.sparse.bsr import BlockSparse


def spmm(bsr: BlockSparse, dense: np.ndarray, interpret: bool | None = None):
    """BSR x dense.  Pads a zero block into every empty block-row (the kernel
    initializes an output row-tile on first visit) and sorts by block-row."""
    m_blocks = bsr.shape[0] // bsr.block_shape[0]
    brows, bcols, blocks = bsr.brows, bsr.bcols, bsr.blocks
    missing = np.setdiff1d(np.arange(m_blocks), brows)
    if len(missing):
        b_m, b_k = bsr.block_shape
        blocks = np.concatenate(
            [blocks, np.zeros((len(missing), b_m, b_k), blocks.dtype)]
        )
        brows = np.concatenate([brows, missing])
        bcols = np.concatenate([bcols, np.zeros(len(missing), np.int64)])
    order = np.argsort(brows, kind="stable")
    return bsr_spmm(
        jnp.asarray(blocks[order]),
        jnp.asarray(brows[order]),
        jnp.asarray(bcols[order]),
        jnp.asarray(dense),
        m_blocks=m_blocks,
        interpret=resolve_interpret(interpret),
    )


def spgemm(
    a: BlockSparse, b: BlockSparse, interpret: bool | None = None
) -> tuple[jnp.ndarray, np.ndarray, np.ndarray]:
    """BSR x BSR -> (C blocks, c_brows, c_bcols).  Inspector on host."""
    pa, pb, pc, crows, ccols = build_pair_lists(a.brows, a.bcols, b.brows, b.bcols)
    if len(pa) == 0:
        bm, bn = a.block_shape[0], b.block_shape[1]
        return jnp.zeros((0, bm, bn), a.blocks.dtype), crows, ccols
    out = bsr_spgemm(
        jnp.asarray(a.blocks),
        jnp.asarray(b.blocks),
        jnp.asarray(pa),
        jnp.asarray(pb),
        jnp.asarray(pc),
        n_c_blocks=len(crows),
        interpret=interpret,
    )
    return out, crows, ccols


def grouped_gemm(x, w, interpret: bool | None = None):
    """(E, C, d) x (E, d, f) -> (E, C, f)."""
    return moe_gemm(
        jnp.asarray(x),
        jnp.asarray(w),
        interpret=resolve_interpret(interpret),
    )


# re-export oracles for test convenience
bsr_spmm_ref = ref.bsr_spmm_ref
bsr_spgemm_ref = ref.bsr_spgemm_ref
moe_gemm_ref = ref.moe_gemm_ref
