"""Sharding rules: logical parameter/activation axes -> mesh axes.

Megatron-style TP on the ``model`` axis, FSDP-style parameter/optimizer
sharding on the ``data`` axis, pure DP on the ``pod`` axis (multi-pod).
Experts (MoE) ride the ``model`` axis (expert parallelism).
"""
from __future__ import annotations

import numpy as np
import jax

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis name -> mesh axis (None = replicated)
LOGICAL_RULES: dict[str, str | tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": "model",  # sequence-parallel regions (MoE entry)
    "embed": None,  # activations' feature axis
    "embed_fsdp": "data",  # weights' feature axis (FSDP)
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": "model",  # sequence-sharded KV cache (distributed flash-decode)
    "head_dim": None,
    "ff": "model",
    "experts": "model",
    "expert_ff": None,
    "vocab": "model",
    "layers": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "conv": None,
}


def spec_for(*logical_axes: str | None, mesh: Mesh) -> P:
    """Translate logical axes to a PartitionSpec valid for ``mesh`` (axes the
    mesh lacks — e.g. 'pod' on the single-pod mesh — are dropped)."""
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
            continue
        phys = LOGICAL_RULES.get(ax, None)
        if phys is None:
            out.append(None)
        elif isinstance(phys, tuple):
            present = tuple(a for a in phys if a in mesh.axis_names)
            out.append(present if len(present) > 1 else (present[0] if present else None))
        else:
            out.append(phys if phys in mesh.axis_names else None)
    return P(*out)


def named(mesh: Mesh, *logical_axes: str | None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(*logical_axes, mesh=mesh))


# ---------------------------------------------------------------------------
# parameter logical-axis trees (mirror the params pytree structure)
# ---------------------------------------------------------------------------
def serve_overlay(axes_tree):
    """Serving shardings: drop the FSDP ('data') axis from weights — decode
    steps must not all-gather parameters every token.  Weights end up
    TP-sharded over 'model' and replicated over 'data'/'pod'."""

    def fix(ax):
        return tuple(None if a == "embed_fsdp" else a for a in ax)

    return jax.tree.map(
        fix,
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def param_logical_axes(cfg) -> dict:
    """Logical axes per parameter; structure mirrors ``init_params``."""
    L = ("layers",)
    axes: dict = {
        "embed": {"tokens": ("vocab", "embed_fsdp")},
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed_fsdp", "vocab")
    layer: dict = {
        "ln1": L + ("embed",),
        "ln2": L + ("embed",),
    }
    if cfg.layer_kind in ("attn", "hybrid"):
        layer["attn"] = {
            "wq": L + ("embed_fsdp", "heads", "head_dim"),
            "wk": L + ("embed_fsdp", "kv_heads", "head_dim"),
            "wv": L + ("embed_fsdp", "kv_heads", "head_dim"),
            "wo": L + ("heads", "head_dim", "embed_fsdp"),
        }
        if cfg.qkv_bias:
            layer["attn"]["bq"] = L + ("heads", "head_dim")
            layer["attn"]["bk"] = L + ("kv_heads", "head_dim")
            layer["attn"]["bv"] = L + ("kv_heads", "head_dim")
    if cfg.layer_kind in ("mamba", "hybrid"):
        layer["ssm"] = {
            "in_proj": L + ("embed_fsdp", "ssm_inner"),
            "gate_proj": L + ("embed_fsdp", "ssm_inner"),
            "conv_w": L + ("conv", "ssm_inner"),
            "x_proj_b": L + ("ssm_inner", "ssm_state"),
            "x_proj_c": L + ("ssm_inner", "ssm_state"),
            "dt_proj": L + ("ssm_inner",),
            "a_log": L + ("ssm_inner", "ssm_state"),
            "d_skip": L + ("ssm_inner",),
            "out_proj": L + ("ssm_inner", "embed_fsdp"),
        }
    if cfg.moe is not None:
        layer["moe"] = {
            "router": L + ("embed", "experts"),
            "wi": L + ("experts", "embed_fsdp", "expert_ff"),
            "wg": L + ("experts", "embed_fsdp", "expert_ff"),
            "wo": L + ("experts", "expert_ff", "embed_fsdp"),
        }
        if cfg.moe.n_shared_experts:
            layer["shared_mlp"] = {
                "wi": L + ("embed_fsdp", "ff"),
                "wg": L + ("embed_fsdp", "ff"),
                "wo": L + ("ff", "embed_fsdp"),
            }
    elif cfg.d_ff > 0:  # d_ff == 0: no FFN sub-block (pure-Mamba archs)
        layer["mlp"] = {
            "wi": L + ("embed_fsdp", "ff"),
            "wo": L + ("ff", "embed_fsdp"),
        }
        if cfg.act in ("swiglu", "geglu"):
            layer["mlp"]["wg"] = L + ("embed_fsdp", "ff")
    axes["layers"] = layer
    return axes


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim (e.g. 4 KV
    heads on a 16-way model axis, vocab 32001): replicate instead."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        kept: list[str] = []
        size = 1
        for a in axes:
            if dim % (size * mesh.shape[a]) == 0:
                kept.append(a)
                size *= mesh.shape[a]
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def fit_sharding_tree(shapes_tree, axes_tree, mesh: Mesh):
    """NamedSharding pytree: logical axes resolved against actual shapes."""
    return jax.tree.map(
        lambda shp, ax: NamedSharding(
            mesh, _fit_spec(spec_for(*ax, mesh=mesh), shp.shape, mesh)
        ),
        shapes_tree,
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


def param_shardings(cfg, mesh: Mesh, serve: bool = False):
    """NamedSharding pytree matching params (shape-aware)."""
    from functools import partial
    from repro.models.transformer import init_params

    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.key(0))
    axes = param_logical_axes(cfg)
    if serve:
        axes = serve_overlay(axes)
    return jax.tree.map(
        lambda shp, ax: NamedSharding(
            mesh, _fit_spec(spec_for(*ax, mesh=mesh), shp.shape, mesh)
        ),
        shapes,
        axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical axes; no-op outside a mesh context
    (CPU smoke tests).  Divisibility-checked against the ambient mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    spec = _fit_spec(spec_for(*logical_axes, mesh=mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def batch_sharding(mesh: Mesh, batch_size: int, ndim: int) -> NamedSharding:
    """Shard the leading (batch) dim over as much of (pod, data) as divides."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    kept: list[str] = []
    size = 1
    for a in axes:
        if batch_size % (size * mesh.shape[a]) == 0:
            kept.append(a)
            size *= mesh.shape[a]
    first = tuple(kept) if len(kept) > 1 else (kept[0] if kept else None)
    return NamedSharding(mesh, P(first, *([None] * (ndim - 1))))
