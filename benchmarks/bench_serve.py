"""Serving-tier benchmarks: batched value streams + the warm-pool loop.

Two claims, two cells:

- ``serve/stream/*`` — the batched-step claim.  An MCL-style iterated
  workload (one structure, fresh values every multiply) through the classic
  one-multiply-per-call path vs the batched executor
  (``PlannedSpGEMM.compile(batch=B)``): B multiplies per dispatch amortize
  the per-call dispatch + collective launch overhead.  The cell asserts
  batched steady-state throughput is >= ``BATCHED_SPEEDUP_FLOOR``x the
  looped path (the ISSUE 8 acceptance number) and records both rates.

- ``serve/loop/*`` — the serving-loop claim.  A ``SpGEMMServer`` drains a
  mixed workload (pool hits + warm replans + cold structures, the three
  regimes production traffic mixes) after a warmup pass that populates the
  warm pool and the batch-bucket executables; the steady phase then measures
  what a warmed service actually delivers: QPS, p50/p99 request latency, and
  batch efficiency (items / padded slots).  ``us_per_call`` is the p99 — the
  number a latency SLO would gate — and ``qps`` is floor-gated by
  ``check_regression.py`` against a machine-calibrated baseline.

Run standalone with forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src:. python benchmarks/bench_serve.py --quick
"""
from __future__ import annotations

import time

import numpy as np

BATCHED_SPEEDUP_FLOOR = 3.0


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _stream_cell(p, n, density, batch, reps, model="fine", seed=0) -> dict:
    """Batched vs looped steady-state on an iterated same-structure stream.

    Both paths ship the same ``batch`` multiplies per timed repetition with
    host packing included (fresh values each call, the MCL regime); only the
    dispatch granularity differs.  Timing is min-of-N over full repetitions
    (heavy-tailed collective stragglers would otherwise dominate the gate).
    """
    import jax

    import repro
    from repro.sparse.structure import random_structure

    rng = np.random.default_rng(seed)
    a_s = random_structure(n, n, density, rng)
    planned = repro.plan(a_s, a_s, p=p, model=model)
    exe_one = planned.compile()
    exe_batch = planned.compile(batch=batch)
    vals = [rng.standard_normal(a_s.nnz).astype(np.float32) for _ in range(batch)]
    stack = np.stack(vals)

    def looped():
        for v in vals:
            jax.block_until_ready(exe_one.runtime(*exe_one.pack(v, v)))

    def batched():
        jax.block_until_ready(exe_batch.runtime(*exe_batch.pack(stack, stack)))

    looped()  # warmup both executables (compiles excluded from timing)
    batched()
    looped_s = _best_of(looped, reps)
    batched_s = _best_of(batched, reps)
    speedup = looped_s / batched_s
    assert speedup >= BATCHED_SPEEDUP_FLOOR, (
        f"batched stream is only {speedup:.1f}x the one-multiply-per-call "
        f"path ({batched_s * 1e6 / batch:.0f} vs {looped_s * 1e6 / batch:.0f} "
        f"us/multiply); the serving tier claims >= {BATCHED_SPEEDUP_FLOOR}x"
    )
    return {
        "name": f"serve/stream/{model}/n{n}/p{p}",
        "status": "ok",
        "us_per_call": int(batched_s / batch * 1e6),
        "looped_us_per_call": int(looped_s / batch * 1e6),
        "qps": int(batch / batched_s),
        "looped_qps": int(batch / looped_s),
        "speedup_vs_looped": round(speedup, 1),
        "batch": batch,
    }


def _loop_cell(p, n, density, requests, structures, model="fine", seed=1) -> dict:
    """Warmed serving loop over mixed traffic: hits + warm replans + colds.

    The warmup pass submits one window per structure so planning, AOT
    compiles, and every batch bucket the steady phase will use are already
    resident; the timed phase then serves ``requests`` mixed requests and
    reports the warmed service's QPS / latency / batch efficiency.
    """
    from repro.launch.serve import ServeStats, SpGEMMServer
    from repro.sparse.structure import random_structure

    from repro.sparse.structure import from_coo

    rng = np.random.default_rng(seed)
    pool = [random_structure(n, n, density, rng) for _ in range(structures)]
    server = SpGEMMServer(p=p, model=model, max_batch=8, batch_window=16, seed=seed)

    def vals(s):
        return (
            rng.standard_normal(s.nnz).astype(np.float32),
            rng.standard_normal(s.nnz).astype(np.float32),
        )

    def perturb(s, frac=0.08):
        """Genuine drift (the MCL/AMG regime): most nonzeros survive, so the
        session warm-starts instead of replanning cold."""
        rows, cols = s.coo()
        keep = rng.random(len(rows)) > frac
        extra = max(1, int(frac * len(rows)))
        return from_coo(
            np.concatenate([rows[keep], rng.integers(0, n, extra)]),
            np.concatenate([cols[keep], rng.integers(0, n, extra)]),
            s.shape,
        )

    # warmup: every structure through every bucket the steady phase uses
    for s in pool:
        for m in (8, 1):
            for _ in range(m):
                va, vb = vals(s)
                server.submit((s, va), (s, vb))
            server.drain()
    # reset the accounting; keep the warm pool and compiled executables
    server.stats = ServeStats()
    server._latencies.clear()
    server._t_first = server._t_last = None
    steady_from = len(server.session.events)

    drift_every = max(8, requests // 4)
    for i in range(requests):
        if i and i % drift_every == 0:
            # mild structure drift mid-stream: absorbed by a warm replan
            pool[i % structures] = perturb(pool[i % structures])
        elif i == (requests // 2) + 1:
            # one cold structure: the worst-case path rides the same p99
            pool[i % structures] = random_structure(n, n, density, rng)
        s = pool[i % structures]
        va, vb = vals(s)
        server.submit((s, va), (s, vb))
        if server.queue_depth >= server.config.batch_window:
            server.step()
    server.drain()
    report = server.report()
    from collections import Counter

    events = dict(Counter(e.kind for e in server.session.events[steady_from:]))
    assert report["completed"] == requests, report
    assert events.get("hit", 0) > 0, "steady phase never hit the warm pool"
    assert events.get("warm_replan", 0) >= 1, events
    return {
        "name": f"serve/loop/{model}/n{n}/p{p}",
        "status": "ok",
        "us_per_call": report["p99_us"],
        "p50_us": report["p50_us"],
        "qps": report["qps"],
        "batch_efficiency": report["batch_efficiency"],
        "dispatches": report["dispatches"],
        "requests": requests,
        "hits": events.get("hit", 0),
        "warm_replans": events.get("warm_replan", 0),
        "cold_replans": events.get("cold_replan", 0),
    }


def _faults_cell(p, n, density, requests, model="fine", seed=2) -> dict:
    """Serving under scripted faults: transient execute failures mid-stream
    are retried by the session policy — every request still completes."""
    from repro.launch.serve import SpGEMMServer
    from repro.resilience import FaultPolicy
    from repro.sparse.structure import random_structure
    from repro.testing import faults

    rng = np.random.default_rng(seed)
    s = random_structure(n, n, density, rng)
    server = SpGEMMServer(
        p=p, model=model, max_batch=4, policy=FaultPolicy(backoff_s=0.0), seed=seed
    )
    with faults.inject("execute", times=2, after=2) as script:
        for _ in range(requests):
            va = rng.standard_normal(s.nnz).astype(np.float32)
            vb = rng.standard_normal(s.nnz).astype(np.float32)
            server.submit((s, va), (s, vb))
        server.drain()
    report = server.report()
    assert script.fired == 2, script.fired
    assert report["completed"] == requests, report
    retries = sum(1 for e in server.session.events if e.kind == "retry")
    assert retries >= 2, retries
    return {
        "name": f"serve/faults/{model}/n{n}/p{p}",
        "status": "ok",
        "us_per_call": report["p99_us"],
        "qps": report["qps"],
        "faults_fired": script.fired,
        "retries": retries,
    }


def run(out_dir: str | None = None, quick: bool = True) -> list[dict]:
    import jax

    from benchmarks.common import emit

    records = []
    if quick:
        p_list, n, density, batch, reps = (4,), 96, 0.06, 8, 8
        requests, structures = 48, 3
    else:
        p_list, n, density, batch, reps = (4, 8), 192, 0.04, 8, 15
        requests, structures = 128, 4
    for p in p_list:
        if jax.device_count() < p:
            records.append(
                {
                    "name": f"serve/all/p{p}",
                    "status": "skipped",
                    "reason": f"{jax.device_count()} device(s) < p={p}",
                }
            )
            continue
        records.append(_stream_cell(p, n, density, batch, reps))
        records.append(_loop_cell(p, n, density, requests, structures))
        records.append(_faults_cell(p, n, density, requests=12))
    emit(records, out_dir, "serve.json")
    return records


if __name__ == "__main__":
    import argparse
    import os

    # the serving loop needs multiple devices: force host devices BEFORE jax
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=8",
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes, p in {4, 8}")
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes (the default)")
    ap.add_argument("--out", default=None, help="artifact dir, e.g. experiments/paper")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run(out_dir=args.out, quick=not args.full):
        print(r)
