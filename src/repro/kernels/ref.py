"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bsr_spmm_ref(
    blocks: jnp.ndarray,  # (nb, bm, bk)
    brows: jnp.ndarray,  # (nb,)
    bcols: jnp.ndarray,  # (nb,)
    dense: jnp.ndarray,  # (K, N)
    m_blocks: int,
) -> jnp.ndarray:
    """A_bsr @ dense -> (m_blocks * bm, N)."""
    nb, bm, bk = blocks.shape
    K, N = dense.shape
    b_tiles = dense.reshape(K // bk, bk, N)
    out = jnp.zeros((m_blocks, bm, N), jnp.promote_types(blocks.dtype, dense.dtype))
    contrib = jnp.einsum("nij,njk->nik", blocks, b_tiles[bcols])
    out = out.at[brows].add(contrib)
    return out.reshape(m_blocks * bm, N)


def bsr_spgemm_ref(
    a_blocks: jnp.ndarray,  # (na, bm, bk)
    b_blocks: jnp.ndarray,  # (nbb, bk, bn)
    pair_a: jnp.ndarray,  # (np,) index into a_blocks
    pair_b: jnp.ndarray,  # (np,) index into b_blocks
    pair_c: jnp.ndarray,  # (np,) index into C block list
    n_c_blocks: int,
) -> jnp.ndarray:
    """Block-sparse x block-sparse -> C blocks (nc, bm, bn).

    The (pair_a, pair_b, pair_c) lists are the inspector output: every
    nontrivial block multiplication and the C block it accumulates into —
    exactly the coarsened multiplication vertices v_(IKJ) of the tiled
    SpGEMM hypergraph.
    """
    # HIGHEST: the TPU's default f32 matmul rounds operands to bf16
    prod = jnp.einsum(
        "nij,njk->nik",
        a_blocks[pair_a],
        b_blocks[pair_b],
        precision=jax.lax.Precision.HIGHEST,
    )
    out = jnp.zeros(
        (n_c_blocks, a_blocks.shape[1], b_blocks.shape[2]),
        jnp.promote_types(a_blocks.dtype, b_blocks.dtype),
    )
    return out.at[pair_c].add(prod)


def moe_gemm_ref(
    x: jnp.ndarray,  # (E, C, d)
    w: jnp.ndarray,  # (E, d, f)
) -> jnp.ndarray:
    """Grouped expert GEMM (the MoE dispatch SpGEMM's dense payload)."""
    return jnp.einsum("ecd,edf->ecf", x, w)
