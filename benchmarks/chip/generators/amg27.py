"""AMG Galerkin product A·P on HPCG's 27-point operator.

A is the 27-point stencil on an n x n x n grid (HPCG's operator); P is the
smoothed-aggregation prolongator: 3x3x3 aggregates (the tentative P0) and
``degree`` steps of damped Jacobi, whose structure is that of
``(I + A)^degree P0``.  Structures are boolean scipy CSR matrices in
canonical order (sorted column indices, no duplicates).

Values: every product takes fresh A and P values, as in re-running AMG
set-up on a fixed mesh with new coefficients.  They are drawn from a
standard normal in float32 from the run's seed.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def canonical(m) -> sp.csr_matrix:
    """Boolean CSR with sorted indices and no duplicates or stored zeros."""
    m = sp.csr_matrix(m, copy=True)
    m.data = np.ones_like(m.data, dtype=bool)
    m.sum_duplicates()
    m.sort_indices()
    m.eliminate_zeros()
    return m


def from_coo(rows, cols, shape) -> sp.csr_matrix:
    data = np.ones(len(rows), dtype=bool)
    return canonical(sp.coo_matrix((data, (rows, cols)), shape=shape))


def stencil27(n: int) -> sp.csr_matrix:
    """27-point stencil on an n^3 grid, one row per grid point."""
    idx = np.arange(n**3).reshape(n, n, n)
    rows, cols = [], []
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                src = idx[max(0, -dx): n - max(0, dx),
                          max(0, -dy): n - max(0, dy),
                          max(0, -dz): n - max(0, dz)]
                dst = idx[max(0, dx): n - max(0, -dx),
                          max(0, dy): n - max(0, -dy),
                          max(0, dz): n - max(0, -dz)]
                rows.append(src.ravel())
                cols.append(dst.ravel())
    return from_coo(np.concatenate(rows), np.concatenate(cols), (n**3, n**3))


def tentative_prolongator(n: int, agg: int) -> sp.csr_matrix:
    """P0: each agg^3 sub-cube of the grid aggregates to one coarse point."""
    if n % agg:
        raise ValueError(f"grid {n} is not divisible by the aggregate {agg}")
    nc = n // agg
    x, y, z = np.unravel_index(np.arange(n**3), (n, n, n))
    coarse = (x // agg) * nc * nc + (y // agg) * nc + (z // agg)
    return from_coo(np.arange(n**3), coarse, (n**3, nc**3))


def smoothed_prolongator(a, p0, degree: int) -> sp.csr_matrix:
    """Structure of (I - w D^-1 A)^degree P0."""
    cur = p0
    a8 = a.astype(np.int8)
    for _ in range(degree):
        c8 = cur.astype(np.int8)
        cur = canonical(a8 @ c8 + c8)
    return cur


def structures(cfg: dict) -> tuple[sp.csr_matrix, sp.csr_matrix]:
    """(A, P) of the configuration."""
    a = stencil27(cfg["grid"])
    p0 = tentative_prolongator(cfg["grid"], cfg["aggregate"])
    return a, smoothed_prolongator(a, p0, cfg["smoothing_degree"])


def value_pool(cfg: dict, a, b, seed: int, size: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """``size`` value sets (A values, P values) in canonical CSR order."""
    rng = np.random.default_rng(seed)
    return [
        (rng.standard_normal(a.nnz, dtype=np.float32),
         rng.standard_normal(b.nnz, dtype=np.float32))
        for _ in range(size)
    ]
