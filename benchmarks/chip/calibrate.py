#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, over many seeds in one
process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds 3 --seeds 101 102 ...

Set-up runs once.  Then for each seed the value pool is built from that
seed, a short window runs on the timed path at the cell's own load, and
the sampled outputs are compared with scipy's product as a run compares
them: that is the program's reading (the lower one).  The control puts the
reference computed in bfloat16 in the program's place for the same value
sets: that is the upper reading.  One JSON line per seed goes to standard
output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the TPU runtime would otherwise write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seed: int, seconds: float) -> dict:
    """One seed's program and control readings of the cell's check."""
    from benchmarks.chip import harness, reference

    cell.pool = cell.generator.value_pool(cell.config, cell.a, cell.b,
                                          harness.seed_int(seed), cell.traffic["pool"])
    win = harness.measure(cell, seconds, seed)
    refs = harness.references(cell, [i for i, _ in win.sample])
    program = max(refs[i].error(got) for i, got in win.sample)
    control = max(
        ref.error(reference.control(cell.a, cell.b, ref.c_keys, *cell.pool[i],
                                    blocks=harness.blocks(cell.config),
                                    positions=ref.positions))
        for i, ref in refs.items()
    )
    return {"seed": seed, "products": len(win.latencies), "sampled": len(win.sample),
            "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    bench = harness.Bench.at(ROOT)
    harness.use_program(ROOT)
    try:
        harness.check_devices(bench.cell(args.workload)["chips"])
    except harness.NoChip as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 1
    harness.enable_compile_cache(bench)
    cell = harness.setup(bench, args.workload, args.seeds[0],
                         log=lambda m: print(m, file=sys.stderr, flush=True))
    print(f"set-up {time.perf_counter() - T0:.1f} s", file=sys.stderr, flush=True)
    for seed in args.seeds:
        t = time.perf_counter()
        rec = readings(cell, seed, args.seconds)
        rec["seconds"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
