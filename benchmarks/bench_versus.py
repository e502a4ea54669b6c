"""Head-to-head: sparsity-dependent ``model="auto"`` vs oblivious SUMMA.

The paper's headline claim as a live gate: a hypergraph partition tuned to
the instance's sparsity must communicate no more than the classic
sparsity-*oblivious* competitor.  For each AMG/LP/MCL instance this suite

1. plans ``model="auto"`` (partitions every executable model, keeps the
   communication-minimal one) and the ``model="summa2d"`` baseline;
2. asserts the measured == predicted identity on BOTH sides — every
   selection record's route-table words equal its connectivity prediction,
   and SUMMA's route tables ship exactly the closed-form
   ``nnz(A)(pc-1) + nnz(B)(pr-1)`` volume — so the comparison below is
   between *verified* numbers, not two cost models;
3. records ``comm_ratio = auto_words / summa_words`` (< 1: the partition
   beats the oblivious broadcast) and, when the process owns >= p devices,
   runs both executors against the dense oracle.

Acceptance (also enforced by ``check_regression.py versus``): auto wins on
at least 2 of the 3 application instances.  SUMMA legitimately wins some
near-dense instances — the suite reports the ratio so that regime stays
visible instead of hidden.

Run standalone with forced host devices to exercise the executors:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/bench_versus.py
"""
from __future__ import annotations

import time

import numpy as np

#: auto must beat (or tie) the oblivious baseline on this many of the three
#: application instances — the suite FAILS otherwise, in any harness
MIN_WINS = 2


def _oracle_exec(handle, a_dense, b_dense, want) -> dict:
    """Compile + run one planned pipeline; report cold wall time + max err."""
    inst = handle.instance
    a_vals = a_dense[inst.a.coo()]
    b_vals = b_dense[inst.b.coo()]
    t0 = time.time()
    got = handle(a_vals, b_vals)
    prefix = handle.model if handle.model == "summa2d" else "auto"
    return {
        f"{prefix}_run_s": round(time.time() - t0, 3),
        f"{prefix}_max_err": float(np.abs(got - want).max()),
    }


def run(out_dir: str | None = None, quick: bool = True) -> list[dict]:
    import repro
    from benchmarks.bench_select import _instances
    from benchmarks.common import emit, random_valued_dense
    from repro.api import device_count
    from repro.distributed.summa import summa_words_ideal

    p = 4 if quick else 8
    rng = np.random.default_rng(0)
    records = []
    wins = 0
    for inst in _instances(quick):
        t0 = time.time()
        auto = repro.plan(inst, p=p, model="auto")
        auto_s = time.time() - t0
        t0 = time.time()
        summa = repro.plan(inst, p=p, model="summa2d")
        summa_s = time.time() - t0

        # measured == predicted on every contestant before comparing them
        for sel in auto.selection:
            assert sel["planned_words"] == sel["predicted_words"], (
                f"{inst.name}/{sel['model']}: planned {sel['planned_words']} "
                f"!= predicted {sel['predicted_words']}"
            )
        s_report = summa.cost_report()
        s_plan = summa.execution_plan
        assert s_report["planned_words"] == s_report["predicted_words"], s_report
        assert s_report["predicted_words"] == summa_words_ideal(
            inst, s_plan.pr, s_plan.pc
        )

        auto_words = auto.cost_report()["predicted_words"]
        summa_words = s_report["predicted_words"]
        win = int(auto_words <= summa_words)
        wins += win
        rec = {
            "name": f"{inst.name}/versus/p{p}",
            "status": "ok",
            "us_per_call": int((auto_s + summa_s) * 1e6),
            "p": p,
            "auto_model": auto.model,
            "auto_words": int(auto_words),
            "summa_words": int(summa_words),
            "summa_mesh": f"{s_plan.pr}x{s_plan.pc}",
            "comm_ratio": round(auto_words / max(summa_words, 1), 4),
            "auto_wins": win,
            "auto_messages": auto.cost_report()["planned_messages"],
            "summa_messages": s_report["planned_messages"],
        }
        if device_count() >= p:
            a_dense = random_valued_dense(inst.a, rng)
            b_dense = random_valued_dense(inst.b, rng)
            want = a_dense @ b_dense
            rec.update(_oracle_exec(auto, a_dense, b_dense, want))
            rec.update(_oracle_exec(summa, a_dense, b_dense, want))
            for k in ("auto_max_err", "summa2d_max_err"):
                assert rec[k] < 1e-2, f"{rec['name']}: {k} = {rec[k]}"
        else:
            rec["run"] = f"skipped ({device_count()} device(s) < p={p})"
        records.append(rec)
    assert wins >= MIN_WINS, (
        f"sparsity-dependent auto beat oblivious SUMMA on only {wins} of "
        f"{len(records)} instances (need >= {MIN_WINS}): "
        + ", ".join(f"{r['name']} ratio={r['comm_ratio']}" for r in records)
    )
    emit(records, out_dir, "versus.json")
    return records


if __name__ == "__main__":
    import argparse
    import os

    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=8",
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale instances")
    ap.add_argument("--quick", action="store_true", help="CI smoke scale")
    ap.add_argument("--out", default=None, help="artifact dir, e.g. experiments/paper")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run(out_dir=args.out, quick=not args.full):
        print(r)
