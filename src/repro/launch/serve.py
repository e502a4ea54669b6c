"""SpGEMM serving loop: warm pool of compiled handles + batched value streams.

The paper's premise makes SpGEMM a compile-once workload: the expensive work
(partition, lower, AOT compile) is per-*structure*, while production traffic
(AMG setup chains, MCL iterations, multi-RHS products) re-runs the same
structure with new values thousands of times.  This module is the traffic
side of that story — a bounded request queue drained by a loop that

- **classifies** every request by structure fingerprint through a
  ``SpGEMMSession`` warm pool (PR 7): an unchanged structure is a pool hit
  (zero planning), a drifted one warm-start-replans, a new one plans cold,
  and the pool's LRU eviction + optional plan store bound memory;
- **batches** same-structure requests into one dispatch through the batched
  executor (``PlannedSpGEMM.compile(batch=n)``): value batches are padded to
  geometric capacity buckets so ragged batch sizes share one AOT executable
  (the runtime LRU from PR 4 holds one executable per bucket);
- **accounts** per-request latency (p50/p99), aggregate throughput (QPS),
  and batch efficiency (items shipped / padded slots), so the serving claim
  is a measured number, not a vibe (``benchmarks/bench_serve.py`` gates it).

Admission is reject-on-full (``QueueFull``): a bounded queue keeps worst-case
latency bounded and pushes overload back to the caller.  Execution failures
go through the session's ``FaultPolicy`` (transients retried with backoff);
a batch that fails permanently marks only its own requests failed — the loop
keeps serving.

Planning-side imports stay jax-free (the PR 5 contract): jax is touched only
when a handle compiles, so ``import repro.launch.serve`` works on a
device-less planning host.

Usage (in-container, forced host devices):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.launch.serve --p 4 --requests 64 --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import OrderedDict

import numpy as np

from repro.resilience import FaultPolicy, retry_call
from repro.sparse.structure import structure_and_values, structure_fingerprint

__all__ = [
    "QueueFull",
    "Request",
    "ServeConfig",
    "ServeStats",
    "SpGEMMServer",
    "serve_spgemm",
]


class QueueFull(RuntimeError):
    """Admission rejection: the bounded request queue is at capacity."""


@dataclasses.dataclass
class ServeConfig:
    """Serving-loop knobs (defaults sized for the in-container smoke)."""

    p: int = 4
    model: str = "auto"
    eps: float = 0.10
    seed: int = 0
    engine: str = "flat"
    max_batch: int = 8  # largest per-dispatch value batch (bucket ceiling)
    batch_window: int = 32  # requests drained per step() across structures
    queue_limit: int = 256  # admission bound; submit() raises QueueFull past it
    pool_entries: int = 8  # warm pool LRU slots (session max_entries)
    store_dir: str | None = None  # plan persistence (survives restarts)
    dtype: str = "float32"
    policy: FaultPolicy | None = None


@dataclasses.dataclass
class Request:
    """One queued multiply: structures + canonical CSR values + timestamps."""

    rid: int
    a_s: object  # SparseStructure
    b_s: object
    a_vals: np.ndarray
    b_vals: np.ndarray
    t_submit: float
    result: np.ndarray | None = None
    error: BaseException | None = None
    t_done: float | None = None

    @property
    def done(self) -> bool:
        return self.t_done is not None

    @property
    def latency_s(self) -> float | None:
        return None if self.t_done is None else self.t_done - self.t_submit


@dataclasses.dataclass
class ServeStats:
    """Aggregate accounting for one server lifetime."""

    submitted: int = 0
    completed: int = 0
    failed: int = 0
    rejected: int = 0
    dispatches: int = 0
    batch_items: int = 0  # real multiplies shipped
    batch_slots: int = 0  # padded capacity those dispatches were compiled for

    @property
    def batch_efficiency(self) -> float:
        """Items shipped / padded batch slots (1.0 == no padding waste)."""
        return self.batch_items / self.batch_slots if self.batch_slots else 0.0


class SpGEMMServer:
    """The serving loop: bounded queue -> structure groups -> batched dispatch.

    ``submit(A, B)`` enqueues a multiply (rejecting when the queue is full);
    ``step()`` drains one batching window — it groups queued requests by
    structure fingerprint, fetches each group's warm pool entry through the
    session (hit / warm replan / cold plan / restore, all on
    ``server.session.events``), and streams each group through the batched
    executor in ``max_batch``-bounded chunks.  ``drain()`` loops ``step()``
    until the queue is empty.  All results land on the ``Request`` objects.
    """

    def __init__(self, config: ServeConfig | None = None, **overrides):
        from repro.distributed.session import SpGEMMSession

        cfg = config or ServeConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.config = cfg
        self.session = SpGEMMSession(
            p=cfg.p,
            model=cfg.model,
            eps=cfg.eps,
            seed=cfg.seed,
            engine=cfg.engine,
            store_dir=cfg.store_dir,
            policy=cfg.policy,
            max_entries=cfg.pool_entries,
            dtype=cfg.dtype,
        )
        self.stats = ServeStats()
        self._queue: OrderedDict[int, Request] = OrderedDict()
        self._latencies: list[float] = []
        self._next_rid = 0
        self._t_first: float | None = None
        self._t_last: float | None = None

    # -- admission ---------------------------------------------------------
    def submit(self, A, B) -> Request:
        """Enqueue C = A @ B.  ``A``/``B`` are dense arrays, scipy sparse
        matrices, or ``(SparseStructure, values)`` pairs.  Raises
        :class:`QueueFull` when the queue is at ``queue_limit`` — overload
        is the caller's problem by design (bounded worst-case latency)."""
        if len(self._queue) >= self.config.queue_limit:
            self.stats.rejected += 1
            raise QueueFull(
                f"queue at capacity ({self.config.queue_limit}); retry after drain"
            )
        a_s, a_vals = structure_and_values(A)
        b_s, b_vals = structure_and_values(B)
        req = Request(
            rid=self._next_rid,
            a_s=a_s,
            b_s=b_s,
            a_vals=np.asarray(a_vals),
            b_vals=np.asarray(b_vals),
            t_submit=time.perf_counter(),
        )
        self._next_rid += 1
        self._queue[req.rid] = req
        self.stats.submitted += 1
        if self._t_first is None:
            self._t_first = req.t_submit
        return req

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # -- the loop ----------------------------------------------------------
    def step(self) -> int:
        """Drain one batching window; returns the number of requests served
        (completed or failed).  Requests leave the queue in FIFO order, but
        same-structure requests inside the window ride one dispatch."""
        window: list[Request] = []
        while self._queue and len(window) < self.config.batch_window:
            _, req = self._queue.popitem(last=False)
            window.append(req)
        if not window:
            return 0
        groups: OrderedDict[str, list[Request]] = OrderedDict()
        for req in window:
            key = f"{structure_fingerprint(req.a_s)}/{structure_fingerprint(req.b_s)}"
            groups.setdefault(key, []).append(req)
        served = 0
        for reqs in groups.values():
            served += self._serve_group(reqs)
        return served

    def drain(self, max_steps: int | None = None) -> int:
        """Run ``step()`` until the queue empties; returns requests served."""
        served = 0
        steps = 0
        while self._queue:
            served += self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return served

    # -- dispatch ----------------------------------------------------------
    def _serve_group(self, reqs: list[Request]) -> int:
        """One structure group: fetch the warm entry, stream the values
        through the batched executor in ``max_batch``-bounded chunks."""
        try:
            entry = self.session.entry_for(reqs[0].a_s, reqs[0].b_s)
        except Exception as exc:
            return self._fail(reqs, exc)
        served = 0
        for i in range(0, len(reqs), self.config.max_batch):
            served += self._dispatch(entry, reqs[i : i + self.config.max_batch])
        return served

    def _dispatch(self, entry, chunk: list[Request]) -> int:
        m = len(chunk)
        try:
            if m == 1:
                # singletons ride the entry's own (unbatched) executable
                exe = entry.exe
                run = lambda: exe(chunk[0].a_vals, chunk[0].b_vals)  # noqa: E731
                capacity = 1
            else:
                exe = entry.planned.compile(batch=m, dtype=self.session.dtype)
                capacity = exe.batch_capacity
                a = np.stack([r.a_vals for r in chunk])
                b = np.stack([r.b_vals for r in chunk])
                run = lambda: exe(a, b)  # noqa: E731
            c = np.asarray(
                retry_call(
                    run,
                    self.session.policy,
                    stage="execute",
                    on_retry=self.session._on_retry,
                )
            )
        except Exception as exc:
            return self._fail(chunk, exc)
        now = time.perf_counter()
        self.stats.dispatches += 1
        self.stats.batch_items += m
        self.stats.batch_slots += capacity
        for i, req in enumerate(chunk):
            req.result = c if m == 1 else c[i]
            req.t_done = now
            self._latencies.append(req.latency_s)
        self.stats.completed += m
        self._t_last = now
        return m

    def _fail(self, reqs: list[Request], exc: BaseException) -> int:
        now = time.perf_counter()
        for req in reqs:
            req.error = exc
            req.t_done = now
        self.stats.failed += len(reqs)
        self._t_last = now
        return len(reqs)

    # -- accounting --------------------------------------------------------
    def report(self) -> dict:
        """Latency / throughput / batching / classification summary."""
        lat = np.asarray(self._latencies) if self._latencies else np.zeros(0)
        elapsed = (
            (self._t_last - self._t_first)
            if self._t_first is not None and self._t_last is not None
            else 0.0
        )
        s = self.stats
        session_stats = self.session.stats()
        return {
            "submitted": s.submitted,
            "completed": s.completed,
            "failed": s.failed,
            "rejected": s.rejected,
            "dispatches": s.dispatches,
            "qps": round(s.completed / elapsed, 1) if elapsed > 0 else 0.0,
            "p50_us": int(np.percentile(lat, 50) * 1e6) if lat.size else 0,
            "p99_us": int(np.percentile(lat, 99) * 1e6) if lat.size else 0,
            "batch_efficiency": round(s.batch_efficiency, 3),
            "pool": session_stats,
        }


def serve_spgemm(workload, config: ServeConfig | None = None, **overrides):
    """Drive a whole workload through one server: submit everything (stepping
    inline when the queue fills), drain, and return (requests, report).

    ``workload`` is an iterable of (A, B) operand pairs.  This is the
    offline/batched entry point — the benchmark and the CLI both use it; a
    live system would call ``submit``/``step`` from its own event loop.
    """
    server = SpGEMMServer(config, **overrides)
    requests = []
    for A, B in workload:
        while True:
            try:
                requests.append(server.submit(A, B))
                break
            except QueueFull:
                server.step()
    server.drain()
    return requests, server.report()


# ---------------------------------------------------------------------------
# CLI: synthetic mixed traffic (pool hits, drifting structures, cold loads)
# ---------------------------------------------------------------------------
def _mixed_workload(n, density, structures, requests, drift, seed):
    """(A, B) pairs mixing the three serving regimes: repeated same-structure
    value streams (pool hits), periodically drifted structures (warm
    replans), and fresh structures (cold plans)."""
    from repro.sparse.structure import random_structure

    rng = np.random.default_rng(seed)
    pool = [random_structure(n, n, density, rng) for _ in range(structures)]

    def drifted(s):
        rows, cols = s.coo()
        keep = rng.random(len(rows)) > drift
        extra = max(1, int(drift * len(rows)))
        from repro.sparse.structure import from_coo

        return from_coo(
            np.concatenate([rows[keep], rng.integers(0, n, extra)]),
            np.concatenate([cols[keep], rng.integers(0, n, extra)]),
            s.shape,
        )

    for i in range(requests):
        if i and i % 16 == 0:
            pool[i % structures] = drifted(pool[i % structures])  # warm replan
        elif i and i % 24 == 0:
            pool[i % structures] = random_structure(n, n, density, rng)  # cold
        s = pool[i % structures]
        vals_a = rng.standard_normal(s.nnz).astype(np.float32)
        vals_b = rng.standard_normal(s.nnz).astype(np.float32)
        yield (s, vals_a), (s, vals_b)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--model", default="fine")
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--density", type=float, default=0.06)
    ap.add_argument("--structures", type=int, default=3)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--drift", type=float, default=0.1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke", action="store_true", help="tiny sizes for a fast in-container run"
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.n, args.requests, args.structures = 48, 24, 2

    from repro.api import device_count
    from repro.launch.compile_cache import enable_compile_cache

    if device_count() < args.p:
        raise SystemExit(
            f"--p {args.p} needs {args.p} devices but {device_count()} are "
            f"visible (on CPU: XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={args.p})"
        )
    enable_compile_cache()

    workload = _mixed_workload(
        args.n, args.density, args.structures, args.requests, args.drift, args.seed
    )
    requests, report = serve_spgemm(
        workload,
        p=args.p,
        model=args.model,
        max_batch=args.max_batch,
        batch_window=args.window,
        seed=args.seed,
    )
    # spot-check one product against numpy so the smoke proves correctness,
    # not just liveness
    done = [r for r in requests if r.result is not None]
    probe = done[len(done) // 2]
    a = np.zeros(probe.a_s.shape, np.float32)
    b = np.zeros(probe.b_s.shape, np.float32)
    a[probe.a_s.coo()] = probe.a_vals
    b[probe.b_s.coo()] = probe.b_vals
    np.testing.assert_allclose(probe.result, a @ b, rtol=1e-4, atol=1e-4)
    print("serve report:")
    for k, v in report.items():
        print(f"  {k}: {v}")
    print("oracle spot-check: OK")
    return report


if __name__ == "__main__":
    main()
