#!/usr/bin/env python3
"""Smoke test of the SpGEMM system on one TPU: kernel, library, partitioner, server.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py              # one chip, phases 1-4 below
    python3 chip_smoke.py --chips 4    # the routed collectives across four chips

Phases (one process; any exception fails the run):

1. kernel: the compiled (Mosaic) BSR pair-list kernel at block 128 on
   blocks tiled from the AMG operator, against its jnp reference;
2. library: ``repro.plan(...).compile()`` for the AMG Galerkin product A.P
   (27-point stencil, the HPCG operator) at p=1 with model="fine", and
   model="monoC" with its default backend and with backend="pallas"; C is
   checked against scipy in canonical CSR order, never densified;
3. partitioner: ``partition(engine="device")`` on the monoC hypergraph,
   within the balance cap, beside ``engine="flat"``;
4. server: ``SpGEMMServer`` answering interior-point LP normal-equation
   requests A.D^2.A^T, each with a fresh diagonal D.

A partition fallback warning is an error here.  The last line of output is
one JSON object naming the device.  Without a TPU the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

RTOL = 1e-4

#: phase 2's AMG grid.  n=96 (884,736 rows) does not fit one v5e: the
#: monoC Pallas path pads each 1x1 tile to a 128-lane row (512 B per
#: scalar); the TPU compiler gives its program 8.6 GB of temporaries at
#: n=72, and they grow with the multiplications (x2.3 at n=96).
LIBRARY_N = 72
LIBRARY_N_REASON = (
    "n=96 would need ~20 GB of TPU memory for the monoC Pallas path "
    "(each 1x1 tile pads to a 512 B lane row); one v5e has 16 GB"
)


def log(msg: str) -> None:
    print(msg, flush=True)


def assert_close(got, want, what: str, rtol: float = RTOL) -> float:
    """``|got - want| <= rtol * (|want| + max|want|)``; returns the max
    relative error (scaled by max|want|)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    scale = float(np.abs(want).max(initial=0.0)) or 1.0
    err = float(np.abs(got - want).max(initial=0.0)) / scale
    bad = np.abs(got - want) > rtol * (np.abs(want) + scale)
    if bad.any():
        raise AssertionError(f"{what}: {int(bad.sum())} entries off (max rel err {err:.3g})")
    return err


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def seeded_values(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def csr_with(structure, values) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.asarray(values, np.float64), structure.indices, structure.indptr),
        shape=structure.shape,
    )


def scipy_c_values(inst, a_vals, b_vals) -> np.ndarray:
    """scipy's A @ B at the C structure's canonical CSR positions."""
    c = (csr_with(inst.a, a_vals) @ csr_with(inst.b, b_vals)).tocsr()
    rows, cols = inst.c.coo()
    return np.asarray(c[rows, cols]).ravel()


# ---------------------------------------------------------------------------
# phase 1: the compiled kernel
# ---------------------------------------------------------------------------
def tile(structure, values, block: int):
    """BSR tiles of a scalar CSR matrix, its shape padded up to ``block``."""
    rows = -(-structure.shape[0] // block) * block
    cols = -(-structure.shape[1] // block) * block
    indptr = np.concatenate(
        [structure.indptr, np.full(rows - structure.shape[0], structure.indptr[-1])]
    )
    m = sp.csr_matrix(
        (values, structure.indices, indptr), shape=(rows, cols)
    ).tobsr(blocksize=(block, block))
    m.sort_indices()
    brows = np.repeat(np.arange(rows // block), np.diff(m.indptr))
    return m.data.astype(np.float32), brows, m.indices.astype(np.int64)


def phase_kernel(n: int, block: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.matrices import amg_instances
    from repro.kernels import resolve_interpret
    from repro.kernels.bsr_spgemm import _bsr_spgemm_jit, build_pair_lists
    from repro.kernels.ref import bsr_spgemm_ref

    inst, _ = amg_instances(n)
    a_blk, a_r, a_c = tile(inst.a, seeded_values(inst.a.nnz, 1), block)
    b_blk, b_r, b_c = tile(inst.b, seeded_values(inst.b.nnz, 2), block)
    pa, pb, pc, c_r, _ = build_pair_lists(a_r, a_c, b_r, b_c)
    interpret = resolve_interpret()
    args = [jnp.asarray(x) for x in (a_blk, b_blk)]
    args += [jnp.asarray(x, jnp.int32) for x in (pa, pb, pc)]
    compiled, compile_s = timed(
        lambda: _bsr_spgemm_jit.lower(
            *args, n_c_blocks=len(c_r), interpret=interpret
        ).compile()
    )
    compiled(*args).block_until_ready()
    got, warm_s = timed(lambda: compiled(*args).block_until_ready())
    want = jax.jit(bsr_spgemm_ref, static_argnames="n_c_blocks")(
        *args, n_c_blocks=len(c_r)
    )
    err = assert_close(got, want, "bsr_spgemm vs bsr_spgemm_ref")
    rec = {
        "amg_n": n,
        "block": block,
        "mode": "interpret" if interpret else "mosaic",
        "a_blocks": len(a_blk),
        "b_blocks": len(b_blk),
        "pairs": len(pa),
        "c_blocks": len(c_r),
        "compile_s": compile_s,
        "warm_call_s": warm_s,
        "max_rel_err": err,
    }
    log(f"phase kernel: {json.dumps(rec)}")
    return rec


# ---------------------------------------------------------------------------
# phase 2: the library path (plan -> compile -> runtime) at deployment size
# ---------------------------------------------------------------------------
def run_runtime(planned, a_vals, b_vals, calls: int, **compile_kwargs) -> tuple[np.ndarray, dict]:
    """Compile ``planned``, call its runtime ``calls`` times on the same
    values, and return C in canonical CSR order plus the timings."""
    from repro.distributed.spgemm_exec import owned_c_values

    exe, compile_s = timed(lambda: planned.compile(**compile_kwargs))
    a, b = exe.pack(a_vals, b_vals)
    c_local, first_s = timed(lambda: exe.runtime(a, b).block_until_ready())
    warm = []
    for _ in range(calls - 1):
        c_local, s = timed(lambda: exe.runtime(a, b).block_until_ready())
        warm.append(s)
    vals = owned_c_values(c_local, planned.execution_plan).reshape(-1)
    mem = exe.runtime._compiled.memory_analysis()  # the compiler's own count
    times = {
        "compile_s": compile_s,
        "first_call_s": first_s,
        "warm_call_s": warm,
        "program_temp_bytes": mem.temp_size_in_bytes,
        "program_argument_bytes": mem.argument_size_in_bytes,
    }
    return vals, times


def phase_library(n: int, calls: int = 3) -> dict:
    import jax

    import repro
    from repro.core.matrices import amg_instances
    from repro.distributed import runtime
    from repro.kernels import resolve_interpret

    (inst, _), build_s = timed(lambda: amg_instances(n))
    a_vals = seeded_values(inst.a.nnz, 1)
    b_vals = seeded_values(inst.b.nnz, 2)
    want, ref_s = timed(lambda: scipy_c_values(inst, a_vals, b_vals))
    log(
        f"phase library: AMG A.P n={n}: {inst.shape[0]} rows, nnz(A)={inst.a.nnz}, "
        f"nnz(P)={inst.b.nnz}, nnz(C)={inst.c.nnz}, {inst.n_mult} multiplications "
        f"(instance {build_s:.1f} s, scipy {ref_s:.1f} s)"
    )
    # the BSR kernel: compiled Mosaic on a TPU (the interpreter on CPU)
    kernel = "interpret" if resolve_interpret() else "pallas"
    results = {}
    for model, backends in (("fine", (None,)), ("monoC", (None, kernel))):
        planned, plan_s = timed(lambda: repro.plan(inst, p=1, model=model))
        got_by_backend = {}
        for backend in backends:
            got, times = run_runtime(planned, a_vals, b_vals, calls, backend=backend)
            err = assert_close(got, want, f"{model}/{backend or 'default'} vs scipy")
            got_by_backend[backend] = got
            rec = {
                "model": model,
                "backend": backend or planned.spec.compile_defaults.get("backend")
                or "platform",
                "plan_s": plan_s,
                **times,
                "max_rel_err": err,
                "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
            }
            results[f"{model}/{rec['backend']}"] = rec
            log(f"phase library: {json.dumps(rec)}")
            del got
            runtime.cache_clear()
        if len(got_by_backend) > 1:
            base, *others = got_by_backend.values()
            for other in others:
                assert_close(other, base, f"{model}: backends disagree")
        del planned, got_by_backend
        gc.collect()
    return results


# ---------------------------------------------------------------------------
# phase 3: the device partitioner
# ---------------------------------------------------------------------------
def phase_partitioner(n: int, p: int, eps: float = 0.10) -> dict:
    from repro.core.matrices import amg_instances
    from repro.core.partition import partition
    from repro.core.spgemm_models import build_model

    inst, _ = amg_instances(n)
    hg = build_model(inst, "monoC")
    total = float(hg.w_comp.sum())
    cap = max((1 + eps) * total / p, float(hg.w_comp.max()))
    rec = {"amg_n": n, "p": p, "vertices": hg.n_vertices, "pins": hg.n_pins}
    for engine in ("device", "flat"):
        res, s = timed(lambda: partition(hg, p, eps=eps, seed=0, engine=engine))
        loads = np.bincount(res.parts, weights=hg.w_comp, minlength=p)
        if loads.max() > cap:
            raise AssertionError(f"{engine}: part load {loads.max()} > cap {cap}")
        rec[engine] = {
            "connectivity": int(res.connectivity),
            "max_load_over_cap": float(loads.max() / cap),
            "seconds": s,
            "phases_s": res.phases,
        }
    log(f"phase partitioner: {json.dumps(rec)}")
    return rec


# ---------------------------------------------------------------------------
# phase 4: the served path
# ---------------------------------------------------------------------------
def phase_server(scale: float, requests: int, max_batch: int = 8) -> dict:
    from repro.core.matrices import lp_instance
    from repro.launch.serve import SpGEMMServer

    inst = lp_instance("fome21", scale=scale)
    a_s, at_s = inst.a, inst.b
    a_vals = seeded_values(a_s.nnz, 3)
    at_vals = csr_with(a_s, a_vals).T.tocsr()
    at_vals.sort_indices()
    at_vals = at_vals.data.astype(np.float32)
    a_cols = a_s.indices
    rng = np.random.default_rng(4)
    server = SpGEMMServer(p=1, model="fine", max_batch=max_batch)
    reqs, d2s = [], []
    for _ in range(requests):
        d2 = rng.uniform(0.5, 2.0, a_s.shape[1]) ** 2  # D^2 of an interior point
        d2s.append(d2)
        # A.D^2 scales column k of A; the right operand stays A^T
        reqs.append(server.submit((a_s, a_vals * d2[a_cols].astype(np.float32)), (at_s, at_vals)))
    _, serve_s = timed(server.drain)
    a = csr_with(a_s, a_vals)
    for req, d2 in zip(reqs, d2s):
        if req.error is not None:
            raise req.error
        want = (a @ sp.diags(d2) @ a.T).toarray()
        assert_close(req.result, want, f"request {req.rid} vs scipy")
    kinds = {e.kind for e in server.session.events}
    bad = kinds & {"engine_fallback", "model_downgrade"}
    if bad:
        raise AssertionError(f"session recorded {sorted(bad)}")
    report = server.report()
    rec = {"shape": list(inst.shape), "requests": requests, "drain_s": serve_s,
           "events": sorted(kinds), "report": report}
    log(f"phase server: {json.dumps(rec, default=str)}")
    return rec


# ---------------------------------------------------------------------------
# --chips 4: the routed all_to_all collectives
# ---------------------------------------------------------------------------
def phase_routed(n: int, p: int) -> dict:
    import repro
    from repro.core.matrices import amg_instances
    from repro.distributed import runtime

    inst, _ = amg_instances(n)
    a_vals = seeded_values(inst.a.nnz, 1)
    b_vals = seeded_values(inst.b.nnz, 2)
    want = scipy_c_values(inst, a_vals, b_vals)
    base, _ = run_runtime(repro.plan(inst, p=1, model="fine"), a_vals, b_vals, 1)
    assert_close(base, want, "fine p=1 vs scipy")
    runtime.cache_clear()
    results = {}
    for model in ("fine", "monoC", "summa2d"):
        planned, plan_s = timed(lambda: repro.plan(inst, p=p, model=model))
        report = planned.cost_report()
        if report["planned_words"] != report["predicted_words"]:
            raise AssertionError(
                f"{model}: measured route words {report['planned_words']} != "
                f"predicted {report['predicted_words']}"
            )
        got, times = run_runtime(planned, a_vals, b_vals, 2)
        err = assert_close(got, want, f"{model} p={p} vs scipy")
        assert_close(got, base, f"{model} p={p} vs fine p=1")
        rec = {"model": model, "p": p, "plan_s": plan_s,
               "predicted_words": report["predicted_words"],
               "measured_words": report["planned_words"], **times, "max_rel_err": err}
        results[model] = rec
        log(f"phase routed: {json.dumps(rec)}")
        del planned, got
        runtime.cache_clear()
        gc.collect()
    return results


@contextlib.contextmanager
def fallbacks_are_errors():
    """Turn the partitioner's fallback warnings (``partition._warn_fallback``)
    into errors, so that no phase passes on a fallback path."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*falling back", category=RuntimeWarning)
        yield


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the routed collectives across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"chip_smoke: needs a TPU, found platform {platform!r}")
        return 1
    if len(devices) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, found {len(devices)}")
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    with fallbacks_are_errors():
        if args.chips == 4:
            phase_routed(48, 4)
        else:
            phase_kernel(48, 128)
            log(f"phase library: AMG n={LIBRARY_N}, not 96: {LIBRARY_N_REASON}")
            phase_library(LIBRARY_N)
            phase_partitioner(48, 4)
            phase_server(1.0, 16)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
