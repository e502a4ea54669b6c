"""Pallas TPU kernel: grouped expert GEMM (E, C, d) x (E, d, f) -> (E, C, f).

The dense payload of the MoE dispatch SpGEMM (the compute the hypergraph
partition schedules onto each expert column).  Standard tiled matmul with an
expert grid axis; K-loop innermost so the fp32 accumulator tile stays
resident in VMEM across K steps.

Grid: (E, C/b_c, f/b_f, d/b_d).  VMEM per step: b_c*b_d + b_d*b_f + b_c*b_f
fp32 tiles; the defaults (128, 128, 512) use ~0.6 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, acc_dtype):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0].astype(acc_dtype),
        w_ref[0].astype(acc_dtype),
        preferred_element_type=acc_dtype,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


@functools.partial(
    jax.jit, static_argnames=("b_c", "b_f", "b_d", "interpret", "acc_dtype")
)
def moe_gemm(
    x: jnp.ndarray,  # (E, C, d)
    w: jnp.ndarray,  # (E, d, f)
    b_c: int = 128,
    b_f: int = 128,
    b_d: int = 512,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jnp.ndarray:
    E, C, d = x.shape
    _, _, f = w.shape
    b_c, b_f, b_d = min(b_c, C), min(b_f, f), min(b_d, d)
    if C % b_c or f % b_f or d % b_d:
        raise ValueError(f"dims ({C},{f},{d}) not divisible by ({b_c},{b_f},{b_d})")
    n_k = d // b_d
    grid = (E, C // b_c, f // b_f, n_k)
    kernel = functools.partial(_kernel, n_k=n_k, acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, b_c, b_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, b_d, b_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, b_c, b_f), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((b_c, b_f), acc_dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(x, w)
