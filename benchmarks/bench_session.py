"""Resilient-session benchmarks: warm vs cold replanning, an MCL-style
drifting loop (optionally with a scripted failure schedule), and the
kill-and-recover cell.

The session's amortization claim extends the paper's: not only does one
partition pay for many same-structure multiplies (``bench_exec.py``), a
*drifted* structure should pay a warm-start replan — label carry-over + one
K-way polish — instead of the full multilevel search.  Cells:

- ``session/warm_replan/*``: planning-only (partition + plan lowering, no
  XLA anywhere) cost of replanning a drifted instance warm vs cold.  This is
  the cell the regression gate tracks, and it asserts warm is at least
  ``WARM_SPEEDUP_FLOOR``x faster.
- ``session_exec/mcl_loop/*``: a full ``repro.session()`` expand-and-prune
  loop — structure drifts every iteration, every product checked against
  numpy.  With ``--faults`` a scripted schedule injects transient failures
  at four stage boundaries mid-loop; the cell asserts they all fired and
  the loop still produced correct products (the resilience acceptance).
- ``session_exec/recover/*``: kill-and-recover — a fresh session on the
  same plan store restores its pool (``restored`` events only) with ZERO
  executor retraces, and the restore path is compared against the cold
  replan it replaces.

Run standalone with forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/bench_session.py --quick --faults
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

WARM_SPEEDUP_FLOOR = 1.5

#: --faults schedule: stage -> 0-based call indices that fail (transient)
FAULT_SCHEDULE = {"partition": [1], "compile": [1], "execute": [2], "store_save": [0]}


def _perturb(struct, rng, frac: float):
    """Drift a structure in place-shape: drop ``frac`` of the nonzeros, add
    the same number of fresh coordinates."""
    from repro.sparse.structure import from_coo

    rows, cols = struct.coo()
    n = len(rows)
    keep = np.ones(n, dtype=bool)
    keep[rng.choice(n, max(1, int(frac * n)), replace=False)] = False
    add = max(1, int(frac * n))
    new_r = rng.integers(0, struct.shape[0], add)
    new_c = rng.integers(0, struct.shape[1], add)
    return from_coo(
        np.concatenate([rows[keep], new_r]),
        np.concatenate([cols[keep], new_c]),
        struct.shape,
    )


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _warm_replan_cell(n, p, density, reps, model="rowwise", seed=0) -> dict:
    """Planning-only: replan a drifted instance cold (full multilevel
    search) vs warm (label carry-over + K-way polish).  Device-independent —
    ``_plan_one`` never touches jax."""
    from repro.api import _plan_one
    from repro.core import SpGEMMInstance
    from repro.sparse.structure import random_structure

    rng = np.random.default_rng(seed)
    a0 = random_structure(n, n, density, rng)
    b = random_structure(n, n, density, rng)
    planned0 = _plan_one(SpGEMMInstance(a0, b), model, p, 0.10, seed, include_nz=False)
    labels = np.asarray(planned0.partition.parts)  # rowwise vertices ARE rows,
    # so the labels align with the drifted instance's vertex set directly
    inst1 = SpGEMMInstance(_perturb(a0, rng, 0.05), b)

    warm_planned = _plan_one(
        inst1, model, p, 0.10, seed, include_nz=False, warm_start=labels
    )
    assert warm_planned.partition.warm, "warm-start fell back to cold at bench scale"
    cold_s = _best_of(
        lambda: _plan_one(inst1, model, p, 0.10, seed, include_nz=False), reps
    )
    warm_s = _best_of(
        lambda: _plan_one(
            inst1, model, p, 0.10, seed, include_nz=False, warm_start=labels
        ),
        reps,
    )
    speedup = cold_s / warm_s
    assert speedup >= WARM_SPEEDUP_FLOOR, (
        f"warm replan {warm_s * 1e6:.0f} us is only {speedup:.2f}x faster than "
        f"cold ({cold_s * 1e6:.0f} us); the drift-aware session claims >= "
        f"{WARM_SPEEDUP_FLOOR}x"
    )
    cold_conn = int(
        _plan_one(inst1, model, p, 0.10, seed, include_nz=False)
        .partition.connectivity
    )
    return {
        "name": f"session/warm_replan/{model}/n{n}/p{p}",
        "status": "ok",
        "us_per_call": int(warm_s * 1e6),
        "cold_us": int(cold_s * 1e6),
        "speedup_vs_cold": round(speedup, 2),
        "warm_connectivity": int(warm_planned.partition.connectivity),
        "cold_connectivity": cold_conn,
    }


def _mcl_seed_matrix(n: int, rng) -> np.ndarray:
    M = (rng.random((n, n)) * (rng.random((n, n)) < 0.15)).astype(np.float32)
    M[np.arange(n), np.arange(n)] = 1.0
    return M


def _mcl_prune(C: np.ndarray, n: int) -> np.ndarray:
    C = C.copy()
    C[C < np.quantile(C[C > 0], 0.3)] = 0.0
    col = C.sum(axis=0)
    M = (C / np.where(col > 0, col, 1.0)).astype(np.float32)
    M[np.arange(n), np.arange(n)] += 0.5
    return M


def _mcl_session_cell(p, n, iters, with_faults: bool, seed=5) -> dict:
    """Full-session MCL loop: drift every iteration, optional scripted
    failures, every product oracle-checked."""
    import contextlib

    import repro
    from repro.resilience import FaultPolicy
    from repro.testing import faults

    store = tempfile.mkdtemp(prefix="bench_session_mcl_")
    try:
        rng = np.random.default_rng(seed)
        M = _mcl_seed_matrix(n, rng)
        s = repro.session(
            p=p, model="rowwise", policy=FaultPolicy(backoff_s=0.0), store_dir=store
        )
        ctx = faults.scripted(FAULT_SCHEDULE) if with_faults else contextlib.nullcontext({})
        t0 = time.perf_counter()
        with ctx as scripts:
            for _ in range(iters):
                C = np.asarray(s.multiply(M, M))
                np.testing.assert_allclose(C, M @ M, rtol=2e-4, atol=2e-4)
                M = _mcl_prune(C, n)
        total_s = time.perf_counter() - t0
        fired = {stage: sc.fired for stage, sc in scripts.items()}
        if with_faults:
            for stage, want in FAULT_SCHEDULE.items():
                assert fired[stage] == len(want), f"{stage} fault never fired"
        counts = s.stats()["events"]
        assert counts.get("cold_replan", 0) + counts.get("warm_replan", 0) == iters
        return {
            "name": f"session_exec/mcl_loop/n{n}/p{p}"
            + ("/faults" if with_faults else ""),
            "status": "ok",
            "us_per_call": int(total_s / iters * 1e6),  # amortized per iteration
            "total_s": round(total_s, 3),
            "iters": iters,
            "warm_replans": counts.get("warm_replan", 0),
            "retries": counts.get("retry", 0),
            "faults_fired": fired,
        }
    finally:
        shutil.rmtree(store, ignore_errors=True)


def _recover_cell(p, n, seed=6) -> dict:
    """Kill-and-recover: session 2 rebuilds its pool from session 1's store
    with zero retraces; restore cost vs the cold replan it replaces."""
    import repro
    from repro.distributed import runtime
    from repro.resilience import FaultPolicy

    store = tempfile.mkdtemp(prefix="bench_session_recover_")
    try:
        rng = np.random.default_rng(seed)
        M = _mcl_seed_matrix(n, rng)
        policy = FaultPolicy(backoff_s=0.0)

        t0 = time.perf_counter()
        s1 = repro.session(p=p, model="rowwise", policy=policy, store_dir=store)
        np.testing.assert_allclose(
            np.asarray(s1.multiply(M, M)), M @ M, rtol=2e-4, atol=2e-4
        )
        cold_s = time.perf_counter() - t0
        del s1  # the crash

        traces0 = runtime.trace_count()
        t0 = time.perf_counter()
        s2 = repro.session(p=p, model="rowwise", policy=policy, store_dir=store)
        np.testing.assert_allclose(
            np.asarray(s2.multiply(M, M)), M @ M, rtol=2e-4, atol=2e-4
        )
        restore_s = time.perf_counter() - t0
        assert runtime.trace_count() == traces0, "restored plan retraced"
        counts = s2.stats()["events"]
        assert counts == {"restored": 1}, counts
        return {
            "name": f"session_exec/recover/n{n}/p{p}",
            "status": "ok",
            "us_per_call": int(restore_s * 1e6),
            "cold_us": int(cold_s * 1e6),
            "speedup_vs_cold": round(cold_s / restore_s, 2),
            "retraces": runtime.trace_count() - traces0,
        }
    finally:
        shutil.rmtree(store, ignore_errors=True)


def run(out_dir: str | None = None, quick: bool = True, with_faults: bool = False):
    import jax

    from benchmarks.common import emit

    if quick:
        n_plan, p_plan, density, reps = 2000, 8, 0.004, 3
        n_exec, p_exec, iters = 96, 4, 5
    else:
        n_plan, p_plan, density, reps = 6000, 8, 0.002, 3
        n_exec, p_exec, iters = 160, 4, 8
    records = [_warm_replan_cell(n_plan, p_plan, density, reps)]
    if jax.device_count() < p_exec:
        records.append(
            {
                "name": f"session_exec/all/p{p_exec}",
                "status": "skipped",
                "reason": f"{jax.device_count()} device(s) < p={p_exec}",
            }
        )
    else:
        records.append(_mcl_session_cell(p_exec, n_exec, iters, with_faults))
        records.append(_recover_cell(p_exec, n_exec))
    emit(records, out_dir, "session.json")
    return records


if __name__ == "__main__":
    import argparse
    import os

    # the exec cells need multiple devices: force them BEFORE jax imports
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=8",
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger planning instances")
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes (the default)")
    ap.add_argument(
        "--faults",
        action="store_true",
        help="run the MCL loop under the scripted failure schedule",
    )
    ap.add_argument("--out", default=None, help="artifact dir, e.g. experiments/paper")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run(out_dir=args.out, quick=not args.full, with_faults=args.faults):
        print(r)
