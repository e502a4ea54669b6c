"""End-to-end training driver.

Usage (in-container, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt

At production scale the same driver runs the full config on the
make_production_mesh topology (multi-controller init happens outside, via the
cluster launcher); everything below is topology-agnostic.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax

import jax.numpy as jnp
import numpy as np

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.configs.shapes import ShapeSpec
from repro.data.pipeline import SyntheticTokens
from repro.launch.elastic import run_loop
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.models.sharding import batch_sharding, param_shardings
from repro.training.optimizer import OPTIMIZERS
from repro.training.step import make_train_step


def build_trainer(cfg, mesh, lr=3e-4, optimizer="adamw"):
    params_sh = param_shardings(cfg, mesh)
    opt_init, _ = OPTIMIZERS[optimizer]
    step = make_train_step(cfg, optimizer=optimizer, lr=lr)
    jitted = jax.jit(step, donate_argnums=(0, 1))
    return jitted, params_sh, opt_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=all_arch_ids())
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(model=args.model_parallel)
    jax.set_mesh(mesh)
    jitted, params_sh, opt_init = build_trainer(
        cfg, mesh, lr=args.lr, optimizer=args.optimizer
    )

    params = jax.jit(partial(init_params, cfg), out_shardings=params_sh)(
        jax.random.key(args.seed)
    )
    opt_state = jax.jit(opt_init)(params)

    data = SyntheticTokens(
        vocab=cfg.vocab,
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        seed=args.seed,
    )
    b_sh = batch_sharding(mesh, args.global_batch, 2)

    def step_fn(state, idx):
        params, opt_state = state
        batch = {
            k: jax.device_put(v, b_sh) for k, v in data.batch(idx).items()
        }
        params, opt_state, metrics = jitted(params, opt_state, batch)
        if idx % 5 == 0 or idx == args.steps - 1:
            print(
                f"step {idx:5d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f}",
                flush=True,
            )
        return params, opt_state

    t0 = time.time()
    (params, opt_state), stats = run_loop(
        (params, opt_state),
        step_fn,
        args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        state_to_tree=lambda s: {"params": s[0], "opt": s[1]},
        tree_to_state=lambda t, s: (
            jax.device_put(t["params"], params_sh),
            jax.tree.map(jnp.asarray, t["opt"]),
        ),
    )
    dt = time.time() - t0
    toks = args.steps * args.global_batch * args.seq_len
    print(
        f"done: {stats.steps_run} steps, {stats.restarts} restarts, "
        f"{toks/dt:.0f} tok/s, {len(stats.stragglers)} straggler events"
    )
    return params


if __name__ == "__main__":
    main()
