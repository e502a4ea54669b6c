import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)
"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, on the 16x16 single-pod mesh and
the 2x16x16 multi-pod mesh:  jit(step).lower(**ShapeDtypeStructs).compile(),
then record memory_analysis(), cost_analysis() and the per-collective byte
census parsed from the compiled HLO.  No arrays are ever allocated.

Usage:
  python -m repro.launch.dryrun --arch qwen3-moe-235b-a22b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]

(The two os.environ lines above MUST run before any jax import — jax locks
the device count at first init.  Override via REPRO_XLA_FLAGS for tests.)
"""
import argparse
import json
import re
import sys
import time
import traceback
from functools import partial

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, all_arch_ids, get_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import init_params, init_kv_cache
from repro.models.sharding import (
    batch_sharding,
    param_logical_axes,
    param_shardings,
    fit_sharding_tree,
    spec_for,
    _fit_spec,
)
from repro.training.optimizer import adamw_init
from repro.training.step import make_train_step, make_prefill_step, make_decode_step

SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([\d,]*)\]")
DTYPE_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1,
}


_CENSUS_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\("
)


def collective_census(hlo_text: str) -> dict:
    """Sum result bytes per collective kind over the compiled module."""
    out: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _CENSUS_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        shapes = SHAPE_RE.findall(m.group(1))  # result type(s) on the lhs
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        rec = out.setdefault(kind, {"count": 0, "result_bytes": 0})
        rec["count"] += 1
        rec["result_bytes"] += nbytes
    return out


def wire_bytes(census: dict, factor_all_reduce: float = 2.0) -> int:
    """Ring-model effective wire bytes: AG/RS/A2A ~ result bytes, AR ~ 2x."""
    total = 0
    for kind, rec in census.items():
        f = factor_all_reduce if kind == "all-reduce" else 1.0
        total += int(rec["result_bytes"] * f)
    return total


def _opt_state_shardings(params_sh, mesh):
    rep = NamedSharding(mesh, P())
    return {
        "mu": params_sh,
        "nu": params_sh,
        "count": rep,
    }


def _cache_logical_axes(cfg):
    ax = {"pos": ()}
    kv_seq = "kv_seq" if cfg.kv_shard_mode == "seq" else "seq"
    if cfg.layer_kind in ("attn", "hybrid"):
        ax["k"] = ("layers", "batch", kv_seq, "kv_heads", "head_dim")
        ax["v"] = ("layers", "batch", kv_seq, "kv_heads", "head_dim")
        ax["cache_pos"] = ("layers", "seq")
    if cfg.layer_kind in ("mamba", "hybrid"):
        ax["conv"] = ("layers", "batch", "conv", "ssm_inner")
        ax["h"] = ("layers", "batch", "ssm_inner", "ssm_state")
    return ax


def build_cell(arch: str, shape: str, mesh, cfg=None, opts=()):
    """Returns (fn, arg_shapes, in_shardings, out_shardings, donate) for the
    cell.  ``opts`` are the §Perf knobs: serve_shardings, donate, remat_dots,
    remat_none, seq_shard."""
    import dataclasses as _dc

    if cfg is None:
        cfg = get_config(arch)
    if "remat_dots" in opts:
        cfg = _dc.replace(cfg, remat_policy="dots")
    if "remat_none" in opts:
        cfg = _dc.replace(cfg, remat_policy="none")
    if "seq_shard" in opts:
        cfg = _dc.replace(cfg, seq_shard_residual=True)
    if "gather_weights" in opts:
        cfg = _dc.replace(cfg, gather_weights=True)
    if "kv_none" in opts:
        cfg = _dc.replace(cfg, kv_shard_mode="none")
    if "kv_seq" in opts:
        cfg = _dc.replace(cfg, kv_shard_mode="seq")
    spec = SHAPES[shape]
    serve = "serve_shardings" in opts and spec.kind in ("prefill", "decode")
    params_shapes = jax.eval_shape(partial(init_params, cfg), jax.random.key(0))
    params_sh = param_shardings(cfg, mesh, serve=serve)
    batch_shapes = input_specs(cfg, shape)
    b_sh = {
        k: batch_sharding(mesh, v.shape[0], v.ndim) for k, v in batch_shapes.items()
    }

    if spec.kind == "train":
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        opt_sh = _opt_state_shardings(params_sh, mesh)
        step = make_train_step(cfg)
        args = (params_shapes, opt_shapes, batch_shapes)
        in_sh = (params_sh, opt_sh, b_sh)
        out_sh = (params_sh, opt_sh, None)
        return step, args, in_sh, out_sh, (0, 1)
    logits_sh = NamedSharding(
        mesh, _fit_spec(P(None, "model"), (spec.global_batch, cfg.vocab), mesh)
    )
    if spec.kind == "prefill":
        step = make_prefill_step(cfg)
        cache_shapes = jax.eval_shape(
            lambda: init_kv_cache(cfg, spec.global_batch, spec.seq_len)
        )
        cache_sh = fit_sharding_tree(cache_shapes, _cache_axes_tree(cfg, cache_shapes), mesh)
        args = (params_shapes, batch_shapes)
        return step, args, (params_sh, b_sh), (logits_sh, cache_sh), ()
    # decode
    step = make_decode_step(cfg)
    cache_shapes = jax.eval_shape(
        lambda: init_kv_cache(cfg, spec.global_batch, spec.seq_len)
    )
    cache_sh = fit_sharding_tree(cache_shapes, _cache_axes_tree(cfg, cache_shapes), mesh)
    args = (params_shapes, cache_shapes, batch_shapes["tokens"])
    in_sh = (params_sh, cache_sh, b_sh["tokens"])
    return step, args, in_sh, (logits_sh, cache_sh), (1,)


def _cache_axes_tree(cfg, cache_shapes):
    ax = _cache_logical_axes(cfg)
    # structure must match exactly (dict keys align by construction)
    return {k: tuple(ax[k]) for k in cache_shapes}


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool,
    out_dir: str | None,
    opts: tuple = (),
) -> dict:
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "opts": list(opts),
        "status": "skipped",
        "reason": why,
    }
    if not ok:
        print(f"[dryrun] SKIP {arch} x {shape} ({why})")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        import dataclasses as _dc

        jax.set_mesh(mesh)  # ambient mesh: with_sharding_constraint sees it
        donate_on = "donate" in opts
        # --- 1. full-depth compile (the deliverable): memory + success ---
        fn, args, in_sh, out_sh, don = build_cell(arch, shape, mesh, opts=opts)
        lowered = jax.jit(
            fn,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=don if donate_on else (),
        ).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem_rec = {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        except Exception as e:  # backend without memory stats
            mem_rec = {"unavailable": str(e)}

        # --- 2. depth-2 / depth-4 unrolled compiles: exact per-layer cost
        # (XLA counts while-loop bodies once; layers are homogeneous, so
        # linear extrapolation in depth is exact — see module docstring) ---
        L = cfg.n_layers
        per_depth = {}
        for u in (2, 4):
            cfg_u = _dc.replace(cfg, n_layers=u, scan_unroll=True)
            fn_u, args_u, in_u, out_u, don_u = build_cell(
                arch, shape, mesh, cfg=cfg_u, opts=opts
            )
            comp_u = (
                jax.jit(
                    fn_u,
                    in_shardings=in_u,
                    out_shardings=out_u,
                    donate_argnums=don_u if donate_on else (),
                )
                .lower(*args_u)
                .compile()
            )
            cost_u = comp_u.cost_analysis()
            if isinstance(cost_u, (list, tuple)):
                cost_u = cost_u[0]
            per_depth[u] = {
                "flops": float(cost_u.get("flops", 0.0)),
                "bytes": float(cost_u.get("bytes accessed", 0.0)),
                "census": collective_census(comp_u.as_text()),
            }

        def _extrap(f2, f4):
            per_layer = (f4 - f2) / 2.0
            return f2 + per_layer * (L - 2)

        flops = _extrap(per_depth[2]["flops"], per_depth[4]["flops"])
        bytes_acc = _extrap(per_depth[2]["bytes"], per_depth[4]["bytes"])
        census = {}
        kinds = set(per_depth[2]["census"]) | set(per_depth[4]["census"])
        for kind in kinds:
            c2 = per_depth[2]["census"].get(kind, {"count": 0, "result_bytes": 0})
            c4 = per_depth[4]["census"].get(kind, {"count": 0, "result_bytes": 0})
            census[kind] = {
                "count": int(round(_extrap(c2["count"], c4["count"]))),
                "result_bytes": int(round(_extrap(c2["result_bytes"], c4["result_bytes"]))),
            }
        n_dev = mesh.devices.size
        rec.update(
            status="ok",
            n_devices=int(n_dev),
            n_layers=L,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem_rec,
            flops=flops,
            bytes_accessed=bytes_acc,
            collectives=census,
            wire_bytes=wire_bytes(census),
            per_depth={str(k): v for k, v in per_depth.items()},
        )
        print(
            f"[dryrun] OK {arch} x {shape} x {mesh_name}: "
            f"flops={flops:.3e} bytes={bytes_acc:.3e} "
            f"wire={rec['wire_bytes']:.3e} "
            f"temp/dev={mem_rec.get('temp_size_in_bytes', 0)/1e9:.2f}GB "
            f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
        print(f"[dryrun]   memory_analysis: {mem_rec}")
        print(f"[dryrun]   collectives(extrap): {json.dumps(census)}")
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}")
        print(f"[dryrun] FAIL {arch} x {shape} x {mesh_name}: {e}")
        traceback.print_exc()
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = ("+" + "+".join(opts)) if opts else ""
        fname = f"{arch}_{shape}_{mesh_name}{tag}.json".replace("/", "_")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=all_arch_ids())
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument(
        "--opt",
        default="",
        help="comma list: serve_shardings,donate,remat_dots,remat_none,seq_shard",
    )
    args = ap.parse_args(argv)
    opts = tuple(o for o in args.opt.split(",") if o)

    archs = all_arch_ids() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out, opts=opts)
                n_fail += rec["status"] == "error"
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
