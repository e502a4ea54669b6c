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


class Reference:
    """scipy's A @ B for one value set, with the per-entry rounding scale."""

    def __init__(self, a, b, c_keys, a_vals, b_vals):
        self.c_keys = c_keys
        self.want = at_structure(csr_with(a, a_vals) @ csr_with(b, b_vals), c_keys)
        self.scale = at_structure(
            csr_with(a, np.abs(a_vals)) @ csr_with(b, np.abs(b_vals)), c_keys
        )
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


def control(a, b, c_keys, a_vals, b_vals) -> np.ndarray:
    """The reference in bfloat16: operands and result rounded to it."""
    return bf16(at_structure(csr_with(a, bf16(a_vals)) @ csr_with(b, bf16(b_vals)), c_keys))
