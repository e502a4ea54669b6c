"""The plain reference of a sparse product and the comparison that decides
``correct``.

The reference is scipy's product of the same operands in float64, read at
the positions of C's canonical CSR order.  It imports nothing of the system
under test and takes nothing it made: the structures come from the
benchmark's own generators.

The number compared is the componentwise error

    max_ij |c_ij - ref_ij| / (|A| |B|)_ij

the error of each entry against the bound that float rounding of its sum
obeys, so a lost or altered term shows on a small entry as plainly as on a
large one.  The control puts this reference, computed in bfloat16, in the
program's place: operands and result rounded to bfloat16, the precision
below the float32 that the configurations state.

A configuration may declare block-sparse operands, r x k blocks of A and
k x c blocks of B (``blocks``).  The structures are then block structures,
the values come as (nnz, r, k) and (nnz, k, c) arrays in block CSR order,
and C's values are read block by block in C's canonical block order,
row-major within a block.  Each operand is expanded to its scalar CSR and
the product and the scale are formed and compared entry by entry as above.
Without ``blocks`` every step is the scalar one.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp


def csr_with(structure: sp.csr_matrix, values) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.asarray(values, np.float64), structure.indices, structure.indptr),
        shape=structure.shape,
    )


def symbolic(a: sp.csr_matrix, b: sp.csr_matrix) -> sp.csr_matrix:
    """C's structure: every (i, j) with some a_ik b_kj (no cancellation)."""
    c = (a.astype(np.int8) @ b.astype(np.int8)).tocsr()
    c.data = np.ones_like(c.data, dtype=bool)
    c.sum_duplicates()
    c.sort_indices()
    return c


def linear_keys(m: sp.csr_matrix) -> np.ndarray:
    rows = np.repeat(np.arange(m.shape[0], dtype=np.int64), np.diff(m.indptr))
    return rows * m.shape[1] + m.indices.astype(np.int64)


def at_structure(m: sp.csr_matrix, c_keys: np.ndarray) -> np.ndarray:
    """``m``'s values at C's canonical positions (0 where ``m`` stores
    nothing, as where scipy dropped an exact cancellation)."""
    m = m.tocsr()
    m.sort_indices()
    keys = linear_keys(m)
    pos = np.searchsorted(c_keys, keys)
    pos = np.minimum(pos, len(c_keys) - 1)
    if len(keys) and not np.array_equal(c_keys[pos], keys):
        raise AssertionError("the reference product has entries outside C's structure")
    out = np.zeros(len(c_keys))
    out[pos] = m.data
    return out


def bsr_with(structure: sp.csr_matrix, values: np.ndarray, block) -> sp.csr_matrix:
    """The scalar CSR of a block operand: ``structure`` over block rows and
    columns, ``values`` of shape (nnz, *block) in its order."""
    r, c = block
    return sp.bsr_matrix(
        (values, structure.indices, structure.indptr),
        shape=(structure.shape[0] * r, structure.shape[1] * c),
    ).tocsr()


class BlockPositions:
    """C's scalar positions, each block's r x c in turn in C's canonical
    block order: ``at(m)`` reads ``m`` there.  Built once for any number of
    value sets.  The positions are held as a sorted scalar CSR whose values
    are the block-major index of each, so the values read in sorted order
    are put back in block-major order."""

    def __init__(self, c_keys: np.ndarray, a, b, blocks):
        block = (blocks[0][0], blocks[1][1])
        n_rows, n_cols = a.shape[0], b.shape[1]
        rows, cols = np.divmod(c_keys, n_cols)
        indptr = np.zeros(n_rows + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
        c = sp.csr_matrix((np.ones(len(c_keys), bool), cols, indptr), shape=(n_rows, n_cols))
        slot = np.arange(len(c_keys) * block[0] * block[1], dtype=np.int64)
        self.scalar = bsr_with(c, slot.reshape(len(c_keys), *block), block)
        self.scalar.sort_indices()

    def at(self, m: sp.csr_matrix) -> np.ndarray:
        m.sort_indices()
        c = self.scalar
        if np.array_equal(m.indptr, c.indptr) and np.array_equal(m.indices, c.indices):
            values = m.data
        else:  # scipy dropped a sum that came out exactly zero
            values = at_structure(m, linear_keys(c))
        out = np.empty(c.nnz)
        out[c.data] = values
        return out


class Reference:
    """scipy's A @ B for one value set, with the per-entry rounding scale.

    ``blocks`` is ((r, k), (k, c)) for block operands, None for scalar ones;
    ``positions``, C's ``BlockPositions``, may be shared by many value sets."""

    def __init__(self, a, b, c_keys, a_vals, b_vals, blocks=None, positions=None):
        self.c_keys = c_keys
        self.positions = positions
        if blocks is None:
            self.want = at_structure(csr_with(a, a_vals) @ csr_with(b, b_vals), c_keys)
            self.scale = at_structure(
                csr_with(a, np.abs(a_vals)) @ csr_with(b, np.abs(b_vals)), c_keys
            )
        else:
            pos = self.positions = positions or BlockPositions(c_keys, a, b, blocks)
            a64 = bsr_with(a, np.asarray(a_vals, np.float64), blocks[0])
            b64 = bsr_with(b, np.asarray(b_vals, np.float64), blocks[1])
            self.want = pos.at(a64 @ b64)
            self.scale = pos.at(abs(a64) @ abs(b64))
        self.scale[self.scale == 0] = np.finfo(np.float64).tiny

    def error(self, got) -> float:
        """Componentwise error of ``got`` (C's values in canonical order);
        inf where it has the wrong shape or a value that is not finite."""
        got = np.asarray(got, np.float64).reshape(-1)
        if got.shape != self.want.shape or not np.isfinite(got).all():
            return float("inf")
        return float((np.abs(got - self.want) / self.scale).max(initial=0.0))


def bf16(x) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def control(a, b, c_keys, a_vals, b_vals, blocks=None, positions=None) -> np.ndarray:
    """The reference in bfloat16: operands and result rounded to it."""
    if blocks is None:
        return bf16(at_structure(csr_with(a, bf16(a_vals)) @ csr_with(b, bf16(b_vals)), c_keys))
    pos = positions or BlockPositions(c_keys, a, b, blocks)
    return bf16(pos.at(bsr_with(a, bf16(a_vals), blocks[0]) @ bsr_with(b, bf16(b_vals), blocks[1])))
