"""Example: batched transformer decode over the training substrate.

Formerly ``repro.launch.serve``; moved here because the library's serving
story is SpGEMM (``python -m repro.launch.serve``), while this driver
exercises the transformer stack (prefill a prompt batch, then decode).

Usage (in-container, reduced config):
  PYTHONPATH=src python examples/transformer_decode.py \
      --arch internlm2-1.8b --smoke --batch 4 --prompt-len 64 --decode-tokens 32
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax

import jax.numpy as jnp
import numpy as np

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.models.sharding import param_shardings
from repro.training.step import make_decode_step, make_prefill_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=all_arch_ids())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-tokens", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(model=args.model_parallel)
    jax.set_mesh(mesh)
    params_sh = param_shardings(cfg, mesh)
    params = jax.jit(partial(init_params, cfg), out_shardings=params_sh)(
        jax.random.key(args.seed)
    )
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32
    )
    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts})
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    key = jax.random.key(args.seed)
    tok = logits.argmax(-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.decode_tokens - 1):
        logits, cache = decode(params, cache, tok)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / args.temperature)[:, None]
            tok = tok.astype(jnp.int32)
        else:
            tok = logits.argmax(-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    total = args.batch * (args.decode_tokens - 1)
    print(
        f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s | "
        f"decode {total} tokens in {t_decode:.2f}s "
        f"({total/max(t_decode,1e-9):.1f} tok/s)"
    )
    toks = jnp.concatenate(out_tokens, axis=1)
    print("first sequence:", np.asarray(toks[0])[:16].tolist())
    return toks


if __name__ == "__main__":
    main()
