"""Pallas kernel tests: shape/dtype sweeps, allclose vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU).

Hypothesis property tests live in ``test_kernels_property.py`` so this
module's deterministic oracle coverage survives environments without
hypothesis installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.bsr_spgemm import build_pair_lists
from repro.sparse.bsr import to_bsr, bsr_to_dense, BlockSparse


def _random_block_dense(rng, m, k, density, block):
    """Dense matrix whose nonzero support is block-structured."""
    gm, gk = m // block, k // block
    mask = rng.random((gm, gk)) < density
    if not mask.any():
        mask[0, 0] = True
    dense = rng.standard_normal((m, k)).astype(np.float32)
    full = np.kron(mask, np.ones((block, block), bool))
    return dense * full


# ---------------------------------------------------------------------------
# bsr_spmm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("mn", [(32, 32, 16), (64, 32, 64)])
def test_bsr_spmm_matches_oracle(block, dtype, mn):
    m, k, n = mn
    rng = np.random.default_rng(0)
    a = _random_block_dense(rng, m, k, 0.4, block).astype(dtype)
    b = rng.standard_normal((k, n)).astype(dtype)
    bsr = to_bsr(np.asarray(a, np.float32), block, block)
    bsr = BlockSparse(bsr.blocks.astype(dtype), bsr.brows, bsr.bcols, bsr.shape)
    got = ops.spmm(bsr, b, interpret=True)
    want = ops.bsr_spmm_ref(
        jnp.asarray(bsr.blocks), jnp.asarray(bsr.brows), jnp.asarray(bsr.bcols),
        jnp.asarray(b), m // block,
    )
    tol = 1e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
    # and the oracle itself matches plain matmul
    np.testing.assert_allclose(
        np.asarray(want, np.float32),
        np.asarray(a, np.float32) @ np.asarray(b, np.float32),
        rtol=tol * 3,
        atol=tol * 3,
    )


# ---------------------------------------------------------------------------
# bsr_spgemm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("shape", [(32, 16, 48), (48, 48, 48)])
def test_bsr_spgemm_matches_dense(block, shape):
    m, k, n = shape
    rng = np.random.default_rng(1)
    a = _random_block_dense(rng, m, k, 0.5, block)
    b = _random_block_dense(rng, k, n, 0.5, block)
    ab, bb = to_bsr(a, block, block), to_bsr(b, block, block)
    c_blocks, crows, ccols = ops.spgemm(ab, bb, interpret=True)
    c = bsr_to_dense(
        BlockSparse(np.asarray(c_blocks), crows, ccols, (m, n))
    )
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pairs_per_call", [1, 5, 7, 1 << 20])
def test_bsr_spgemm_chunked_calls_match_dense(monkeypatch, pairs_per_call):
    """Pair lists longer than one call's SMEM share run as a loop of calls
    that accumulate into the running C: a C tile whose run of pairs spans a
    chunk boundary, a ragged last chunk, and C blocks no pair reaches (zero)
    all agree with the dense product."""
    from repro.kernels import bsr_spgemm as mod

    rng = np.random.default_rng(7)
    block = 8
    a = _random_block_dense(rng, 40, 32, 0.5, block)
    b = _random_block_dense(rng, 32, 24, 0.5, block)
    ab, bb = to_bsr(a, block, block), to_bsr(b, block, block)
    pa, pb, pc, crows, ccols = build_pair_lists(ab.brows, ab.bcols, bb.brows, bb.bcols)
    monkeypatch.setattr(mod, "PAIRS_PER_CALL", pairs_per_call)
    mod._bsr_spgemm_jit.clear_cache()
    try:
        # one spare C block that no pair touches
        got = mod.bsr_spgemm(ab.blocks, bb.blocks, pa, pb, pc, len(crows) + 1)
    finally:
        mod._bsr_spgemm_jit.clear_cache()
    got = np.asarray(got)
    np.testing.assert_array_equal(got[-1], 0)
    c = bsr_to_dense(BlockSparse(got[:-1], crows, ccols, (40, 24)))
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)


def test_resolve_interpret_follows_the_platform(monkeypatch):
    """CPU runs the interpreter, TPU the compiled kernel, and an explicit
    interpreter request on a TPU is refused."""
    from repro.kernels import resolve_interpret

    assert resolve_interpret() is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        resolve_interpret(True)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        resolve_interpret()


def test_bsr_spgemm_pair_list_int32_cast_covers_all_operand_kinds():
    """The host-side int32 cast is one explicit helper: int64 ndarrays and
    Python lists cast host-side (no convert inside jit), already-int32
    traced operands pass through untouched, and other traced int dtypes get
    a single astype — all three kinds produce identical results."""
    from repro.kernels.bsr_spgemm import _pair_list_int32, bsr_spgemm

    # helper semantics per operand kind
    out = _pair_list_int32(np.array([0, 1, 2], dtype=np.int64))
    assert out.dtype == jnp.int32
    out = _pair_list_int32([0, 1, 2])
    assert out.dtype == jnp.int32
    traced32 = jnp.array([0, 1, 2], dtype=jnp.int32)
    assert _pair_list_int32(traced32) is traced32  # no-op, no copy
    assert _pair_list_int32(jnp.array([0, 1], dtype=jnp.int16)).dtype == jnp.int32

    # end to end: the kernel result is identical through every kind
    rng = np.random.default_rng(4)
    block = 8
    a = _random_block_dense(rng, 32, 16, 0.5, block)
    b = _random_block_dense(rng, 16, 24, 0.5, block)
    ab, bb = to_bsr(a, block, block), to_bsr(b, block, block)
    from repro.kernels.bsr_spgemm import build_pair_lists

    pa, pb, pc, crows, ccols = build_pair_lists(ab.brows, ab.bcols, bb.brows, bb.bcols)
    n_c = len(crows)
    want = bsr_spgemm(ab.blocks, bb.blocks, pa, pb, pc, n_c, interpret=True)
    as_list = bsr_spgemm(
        ab.blocks, bb.blocks, list(pa), list(pb), list(pc), n_c, interpret=True
    )
    as_jnp = bsr_spgemm(
        ab.blocks,
        bb.blocks,
        jnp.asarray(pa, jnp.int32),
        jnp.asarray(pb, jnp.int32),
        jnp.asarray(pc, jnp.int32),
        n_c,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(want), np.asarray(as_list))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(as_jnp))


def test_bsr_spgemm_pair_list_is_tiled_hypergraph():
    """The inspector's pair list cardinality equals |V^m| of the coarsened
    (block-level) SpGEMM hypergraph."""
    from repro.core import SpGEMMInstance
    from repro.sparse import from_coo

    rng = np.random.default_rng(2)
    block = 8
    a = _random_block_dense(rng, 40, 32, 0.4, block)
    b = _random_block_dense(rng, 32, 24, 0.4, block)
    ab, bb = to_bsr(a, block, block), to_bsr(b, block, block)
    pa, pb, pc, crows, ccols = build_pair_lists(ab.brows, ab.bcols, bb.brows, bb.bcols)
    inst = SpGEMMInstance(ab.block_structure(), bb.block_structure())
    assert len(pa) == inst.n_mult
    assert len(crows) == inst.c.nnz


# ---------------------------------------------------------------------------
# moe_gemm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 16, 32, 24), (4, 128, 64, 16)])
def test_moe_gemm_matches_oracle(dtype, shape):
    E, C, d, f = shape
    rng = np.random.default_rng(3)
    x = rng.standard_normal((E, C, d)).astype(dtype)
    w = rng.standard_normal((E, d, f)).astype(dtype)
    got = ops.grouped_gemm(x, w, interpret=True)
    want = ops.moe_gemm_ref(jnp.asarray(x), jnp.asarray(w))
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
