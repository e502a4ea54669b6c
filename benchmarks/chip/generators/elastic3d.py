"""Smoothed-aggregation AMG's first Galerkin product A·P for 3D linear
elasticity, in blocks.

Q1 hexahedra on an n x n x n node grid couple each node to its 27
neighbours, and each node carries 3 displacements, so A has the node
structure of the 27-point stencil and a 3 x 3 block at each nonzero.  P is
the smoothed prolongator over 3x3x3-node aggregates with the 6 rigid-body
modes as the near-null space: a 3 x 6 block at each nonzero of the scalar
smoothed-aggregation structure.  The node structures are amg27's, read from
that generator.  The configuration's ``blocks`` gives the block shapes.

Values: every product takes fresh A and P blocks, as in re-running AMG
set-up on a fixed mesh with new coefficients: standard normal float32 from
the run's seed, (nnz(A), 3, 3) and (nnz(P), 3, 6) in block CSR order.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmarks.chip.harness import load_module

amg27 = load_module(Path(__file__).with_name("amg27.py"))


def structures(cfg: dict):
    """(A, P) node structures: boolean canonical CSR, one entry a block."""
    return amg27.structures(cfg)


def value_pool(cfg: dict, a, b, seed: int, size: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """``size`` value sets (A blocks, P blocks) in block CSR order."""
    rng = np.random.default_rng(seed)
    a_block, b_block = (tuple(cfg["blocks"][key]) for key in ("a", "b"))
    return [
        (rng.standard_normal((a.nnz, *a_block), dtype=np.float32),
         rng.standard_normal((b.nnz, *b_block), dtype=np.float32))
        for _ in range(size)
    ]
