"""Layer primitives: norms, RoPE, chunked causal attention (GQA + sliding
window), SwiGLU/GeLU MLP, expert-parallel MoE, Mamba-1 selective SSM.

Functional style: each layer is (params, x, ...) -> y; parameters live in
plain dict pytrees created by ``transformer.init_params``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax

import jax.numpy as jnp
import numpy as np

from repro.compat import shard_map
from repro.models.sharding import constrain


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * w


def apply_norm(kind: str, x, w):
    return rms_norm(x, w) if kind == "rms" else layer_norm(x, w)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float, positions: jnp.ndarray) -> tuple:
    """positions: (...,) -> cos/sin of shape (..., d_head//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, Dh); cos/sin: (B?, S, Dh//2) broadcastable."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., None, :]  # (B, S, 1, Dh//2)
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked causal attention (flash-style online softmax, pure JAX)
# ---------------------------------------------------------------------------
def chunked_attention(
    q: jnp.ndarray,  # (B, S, H, Dh)
    k: jnp.ndarray,  # (B, S, KVH, Dh)
    v: jnp.ndarray,  # (B, S, KVH, Dh)
    window: int = 0,  # 0 = full causal
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    B, S, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH  # query groups per kv head
    scale = 1.0 / np.sqrt(Dh)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    nq, nk = S // q_chunk, S // kv_chunk
    if S % q_chunk or S % kv_chunk:
        raise ValueError(f"S={S} not divisible by chunks {q_chunk}/{kv_chunk}")

    # (B, nq, qc, KVH, G, Dh)
    qr = q.reshape(B, nq, q_chunk, KVH, G, Dh)
    kr = k.reshape(B, nk, kv_chunk, KVH, Dh)
    vr = v.reshape(B, nk, kv_chunk, KVH, Dh)

    def per_q_chunk(qi, q_blk):
        # online softmax over kv chunks
        def step(carry, ki):
            m, l, acc = carry
            k_blk = kr[:, ki]  # (B, kc, KVH, Dh)
            v_blk = vr[:, ki]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk) * scale
            # causal / sliding-window mask between absolute positions
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m) - m_safe)
            corr = jnp.where(jnp.isinf(m), 0.0, corr)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, q_chunk), -jnp.inf, dtype=jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_chunk), dtype=jnp.float32)
        acc0 = jnp.zeros((B, KVH, G, q_chunk, Dh), dtype=jnp.float32)
        # only kv chunks that can be visible to this q chunk
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return out  # (B, KVH, G, qc, Dh)

    outs = jax.lax.map(lambda qi: per_q_chunk(qi, qr[:, qi]), jnp.arange(nq))
    # (nq, B, KVH, G, qc, Dh) -> (B, S, H, Dh)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)
    return out.reshape(B, KVH * G, S, Dh).transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, H, Dh)
    k_cache: jnp.ndarray,  # (B, C, KVH, Dh)
    v_cache: jnp.ndarray,  # (B, C, KVH, Dh)
    cache_pos: jnp.ndarray,  # (C,) absolute positions, -1 = empty slot
    cur_pos: jnp.ndarray,  # () current absolute position
    window: int = 0,
) -> jnp.ndarray:
    B, _, H, Dh = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / np.sqrt(Dh)
    qr = q.reshape(B, KVH, G, Dh)
    s = jnp.einsum("bhgd,bchd->bhgc", qr, k_cache).astype(jnp.float32) * scale
    valid = (cache_pos >= 0) & (cache_pos <= cur_pos)
    if window:
        valid &= cur_pos - cache_pos < window
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgc,bchd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, Dh)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp(params: dict, x: jnp.ndarray, act: str) -> jnp.ndarray:
    h = x @ params["wi"]
    if act in ("swiglu", "geglu"):
        g = x @ params["wg"]
        gate = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
        h = h * gate
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", "seq", "ff")
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# MoE: top-k routing with capacity + expert-parallel grouped GEMM
# ---------------------------------------------------------------------------
def _moe_dispatch_combine(xt, fe, ft, fg, wi, wg, wo, n_experts, cap, act_dtype):
    """Shared dispatch -> grouped GEMM -> combine on sorted (expert, token,
    gate) pair lists.  fe must be sorted ascending; fe == n_experts marks
    dropped/foreign pairs."""
    T, d = xt.shape
    pos_in_e = jnp.arange(len(fe)) - jnp.searchsorted(fe, fe, side="left")
    keep = (pos_in_e < cap) & (fe < n_experts)
    slot = jnp.where(keep, fe * cap + pos_in_e, n_experts * cap)
    buf = jnp.zeros((n_experts * cap + 1, d), act_dtype).at[slot].add(
        (xt[ft] * keep[:, None]).astype(act_dtype)
    )
    expert_in = buf[:-1].reshape(n_experts, cap, d)

    h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
    g = jnp.einsum("ecd,edf->ecf", expert_in, wg)
    h = h * jax.nn.silu(g)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo)  # (E, cap, d)

    flat_out = expert_out.reshape(n_experts * cap, d)
    contrib = flat_out[jnp.minimum(slot, n_experts * cap - 1)] * (fg * keep)[:, None]
    return jnp.zeros((T, d), act_dtype).at[ft].add(contrib.astype(act_dtype))


def _sorted_pairs(gate_idx, gate_vals, T, K):
    flat_expert = gate_idx.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert)
    return flat_expert[order], flat_token[order], flat_gate[order]


def moe_layer(
    params: dict,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_loss).

    Token-dropping capacity MoE with sort-based dispatch (no (T, E, C)
    one-hot tensor).  Two execution paths:

    - expert-parallel shard_map (default under a mesh with a 'model' axis
      that divides n_experts): tokens stay batch-sharded and replicated
      across the model axis; each model column selects the pairs routed to
      its local experts, runs the grouped GEMM, and the combine is one psum
      over 'model'.  This is the dispatch schedule the hypergraph comm
      planner models (monochrome-B coarsening = expert ownership).
    - plain GSPMD fallback (no mesh / indivisible): correct everywhere, but
      XLA materializes and reduces the global dispatch buffer — the measured
      naive baseline in EXPERIMENTS.md §Perf.
    """
    moe = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = moe.n_experts, moe.top_k
    xt = x.reshape(T, d)

    logits = (xt @ params["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros(E, probs.dtype).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce) * moe.router_aux_coef

    # expert placement permutation (hypergraph comm planner, beyond-paper):
    # decides which experts co-reside on a model column
    if moe.expert_placement is not None:
        perm = jnp.asarray(np.asarray(moe.expert_placement))
        gate_idx = perm[gate_idx]

    mesh = jax.sharding.get_abstract_mesh()
    ep_ok = (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and E % mesh.shape["model"] == 0
    )
    if ep_ok:
        out = _moe_ep(xt, gate_idx, gate_vals, params, cfg, mesh)
    else:
        cap = int(np.ceil(T * K / E * moe.capacity_factor))
        fe, ft, fg = _sorted_pairs(gate_idx, gate_vals, T, K)
        out = _moe_dispatch_combine(
            xt, fe, ft, fg, params["wi"], params["wg"], params["wo"], E, cap, xt.dtype
        )
    return out.reshape(B, S, d), aux


def _moe_ep(xt, gate_idx, gate_vals, params, cfg, mesh):
    """Expert-parallel dispatch via shard_map (see moe_layer docstring)."""
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    T, d = xt.shape
    E, K = moe.n_experts, moe.top_k
    tp = mesh.shape["model"]
    E_loc = E // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    T_loc = T // n_batch if T % n_batch == 0 else T
    tok_spec = P(bspec, None) if T % n_batch == 0 else P(None, None)
    cap = int(np.ceil(max(T_loc, 1) * K / E * moe.capacity_factor))

    d_fsdp = (
        "data" in mesh.axis_names
        and d % mesh.shape["data"] == 0
        and mesh.shape["data"] > 1
    )
    wi_spec = P("model", "data" if d_fsdp else None, None)
    wo_spec = P("model", None, "data" if d_fsdp else None)

    def body(xt_loc, gi_loc, gv_loc, wi_loc, wg_loc, wo_loc):
        # weights at rest are FSDP-sharded on d; gather d before compute
        if d_fsdp:
            wi_full = jax.lax.all_gather(wi_loc, "data", axis=1, tiled=True)
            wg_full = jax.lax.all_gather(wg_loc, "data", axis=1, tiled=True)
            wo_full = jax.lax.all_gather(wo_loc, "data", axis=2, tiled=True)
        else:
            wi_full, wg_full, wo_full = wi_loc, wg_loc, wo_loc
        col = jax.lax.axis_index("model")
        local_e = gi_loc - col * E_loc
        mine = (local_e >= 0) & (local_e < E_loc)
        t_loc = xt_loc.shape[0]
        fe_all = jnp.where(mine, local_e, E_loc).reshape(-1)
        order = jnp.argsort(fe_all)
        fe = fe_all[order]
        ft = jnp.repeat(jnp.arange(t_loc), K)[order]
        fg = gv_loc.reshape(-1)[order]
        out = _moe_dispatch_combine(
            xt_loc, fe, ft, fg, wi_full, wg_full, wo_full, E_loc, cap, xt_loc.dtype
        )
        # combine across expert columns: one psum over 'model'
        out = jax.lax.psum(out.astype(jnp.float32), "model")
        return out.astype(xt_loc.dtype)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, wi_spec, wi_spec, wo_spec),
        out_specs=tok_spec,
    )(xt, gate_idx, gate_vals, params["wi"], params["wg"], params["wo"])


# ---------------------------------------------------------------------------
# Mamba-1 selective SSM
# ---------------------------------------------------------------------------
def _causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, Di); w: (Kc, Di) depthwise causal conv, as a sum of shifted
    copies (Kc is tiny — 4)."""
    Kc = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(Kc):
        shift = Kc - 1 - i
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + xs * w[i]
    return out


def mamba_scan(
    a: jnp.ndarray,  # (B, S, Di, N) decay = exp(dt * A)
    bx: jnp.ndarray,  # (B, S, Di, N) input contribution dt * B_t * x_t
    h0: jnp.ndarray,  # (B, Di, N)
    chunk: int = 256,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked linear recurrence h_t = a_t * h_{t-1} + bx_t.

    lax.scan over chunks (sequential carry), associative_scan within chunks
    (parallel): compile-friendly and TPU-parallel.  Returns (h_all, h_last).
    """
    B, S, Di, N = a.shape
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nc = S // chunk
    ar = a.reshape(B, nc, chunk, Di, N)
    br = bx.reshape(B, nc, chunk, Di, N)

    def combine(u, v):
        (ua, ub), (va, vb) = u, v
        return ua * va, ub * va + vb

    def chunk_step(h, inp):
        ac, bc = inp  # (B, chunk, Di, N)
        pa, pb = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h_all = pa * h[:, None] + pb  # (B, chunk, Di, N)
        return h_all[:, -1], h_all

    h_last, h_chunks = jax.lax.scan(
        chunk_step, h0, (jnp.moveaxis(ar, 1, 0), jnp.moveaxis(br, 1, 0))
    )
    h_all = jnp.moveaxis(h_chunks, 0, 1).reshape(B, S, Di, N)
    return h_all, h_last


def mamba_block_with_state(
    params: dict,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
    chunk: int = 256,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Mamba-1 block.  Returns (y, conv_tail (B, Kc-1, Di), h_last)."""
    xz = constrain(x @ params["in_proj"], "batch", "seq", "ssm_inner")
    z = x @ params["gate_proj"]  # (B, S, Di)
    xc = _causal_conv(xz, params["conv_w"])
    xc = constrain(jax.nn.silu(xc), "batch", "seq", "ssm_inner")
    # data-dependent SSM parameters
    bt = xc @ params["x_proj_b"]  # (B, S, N)
    ct = xc @ params["x_proj_c"]  # (B, S, N)
    dt = jax.nn.softplus(xc * params["dt_proj"]).astype(jnp.float32)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))  # (Di, N)
    # the linear recurrence runs in fp32 (SSM stability + uniform scan dtypes)
    decay = jnp.exp(dt[..., None] * a)  # (B, S, Di, N) fp32
    bx = (dt * xc.astype(jnp.float32))[..., None] * bt.astype(jnp.float32)[
        ..., None, :
    ]
    h0 = jnp.zeros((x.shape[0], decay.shape[2], decay.shape[3]), jnp.float32)
    h_all, h_last = mamba_scan(decay, bx, h0, chunk=chunk)
    y = jnp.einsum("bsdn,bsn->bsd", h_all, ct.astype(jnp.float32)).astype(
        x.dtype
    ) + xc * params["d_skip"]
    y = y * jax.nn.silu(z)
    Kc = params["conv_w"].shape[0]
    conv_tail = xz[:, -(Kc - 1) :, :]
    return (y @ params["out_proj"]).astype(x.dtype), conv_tail, h_last


def mamba_block(params: dict, x: jnp.ndarray, cfg, chunk: int = 256) -> jnp.ndarray:
    y, _, _ = mamba_block_with_state(params, x, cfg, chunk=chunk)
    return y


def mamba_decode_step(
    params: dict,
    x: jnp.ndarray,  # (B, 1, d)
    conv_state: jnp.ndarray,  # (B, Kc-1, Di)
    h: jnp.ndarray,  # (B, Di, N)
    cfg,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token Mamba step with carried (conv_state, h)."""
    xz = x @ params["in_proj"]  # (B, 1, Di)
    z = x @ params["gate_proj"]
    w = params["conv_w"]  # (Kc, Di)
    Kc = w.shape[0]
    full = jnp.concatenate([conv_state, xz], axis=1)  # (B, Kc, Di)
    xc = jax.nn.silu((full * w[None]).sum(axis=1, keepdims=True))  # (B,1,Di)
    new_conv = full[:, 1:]
    bt = xc @ params["x_proj_b"]
    ct = xc @ params["x_proj_c"]
    dt = jax.nn.softplus(xc * params["dt_proj"]).astype(jnp.float32)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt[:, 0, :, None] * a)  # (B, Di, N) fp32
    bx = (dt * xc.astype(jnp.float32))[:, 0, :, None] * bt.astype(jnp.float32)[
        :, 0, None, :
    ]
    h_new = decay * h + bx  # h carried in fp32
    y = jnp.einsum("bdn,bn->bd", h_new, ct[:, 0].astype(jnp.float32)).astype(
        x.dtype
    )[:, None] + xc * params["d_skip"]
    y = y * jax.nn.silu(z)
    return (y @ params["out_proj"]).astype(x.dtype), new_conv, h_new
