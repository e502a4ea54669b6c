"""End-to-end model selection sweep: predicted vs measured communication.

For each AMG/LP/MCL instance, partition *every* hypergraph model, lower all
seven (the full registry is executable) to plans, count the words their
routing tables ship, and — when the process owns enough devices — run the
executors against the dense oracle.  The suite's acceptance assertion is
the paper's central claim made executable: for the replicated-free plans
(every model but rowwise) the measured words equal the
connectivity metric the partitioner minimized, exactly; rowwise matches
through its nnz-weighted useful words.

Run standalone with forced host devices to exercise the executors:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/bench_select.py

Under ``run.py`` (single device) the executor cells are skipped; the
predicted == measured assertion is device-independent and always runs.
"""
from __future__ import annotations

import numpy as np

from repro.distributed.registry import MODEL_SPECS

# replicated-free plans: every shipped item is one nonzero payload (or one C
# partial), so the words on the wire (minus padding) are exactly the
# connectivity cost
EXACT_MODELS = tuple(n for n, s in MODEL_SPECS.items() if s.measured == "exact")
# rowwise's nnz-weighted useful words also reproduce its model's
# prediction; asserted too, reported separately
USEFUL_EXACT_MODELS = tuple(n for n, s in MODEL_SPECS.items() if s.measured == "useful")


def _instances(quick: bool):
    from repro.core.matrices import amg_instances, lp_instance, mcl_instance

    if quick:
        yield amg_instances(6)[0]
        yield lp_instance("fome21", scale=0.02)
        yield mcl_instance("facebook", scale=0.02)
    else:
        yield from amg_instances(9)
        yield lp_instance("fome21", scale=0.05)
        yield mcl_instance("facebook", scale=0.06)


def run(out_dir: str | None = None, quick: bool = True) -> list[dict]:
    from benchmarks.common import PIN_CAP, emit, random_valued_dense
    from repro.distributed.select import sweep_instance

    records = []
    p_list = (4,) if quick else (4, 8)
    rng = np.random.default_rng(0)
    for inst in _instances(quick):
        a_dense = random_valued_dense(inst.a, rng)
        b_dense = random_valued_dense(inst.b, rng)
        for p in p_list:
            recs = sweep_instance(
                inst,
                p,
                a_dense=a_dense,
                b_dense=b_dense,
                execute=True,
                pin_cap=PIN_CAP,
            )
            for rec in recs:
                if rec["status"] != "ok":
                    continue
                model = rec["model"]
                if model in EXACT_MODELS + USEFUL_EXACT_MODELS and "measured_words" in rec:
                    assert rec["measured_words"] == rec["predicted_words"], (
                        f"{rec['name']}: measured {rec['measured_words']} != "
                        f"predicted {rec['predicted_words']}"
                    )
                    rec["measured_eq_predicted"] = True
                if "exec_max_err" in rec:
                    assert rec["exec_max_err"] < 1e-2, (
                        f"{rec['name']}: executor diverged from the oracle "
                        f"(max err {rec['exec_max_err']})"
                    )
            records.extend(recs)
    emit(records, out_dir, "select.json")
    return records


if __name__ == "__main__":
    import argparse
    import os

    # executors need multiple devices: force host devices BEFORE jax imports
    # (safe here — standalone entry, jax not yet imported via repro)
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=8",
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale instances")
    ap.add_argument("--out", default=None, help="artifact dir, e.g. experiments/paper")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run(out_dir=args.out, quick=not args.full):
        print(r)
