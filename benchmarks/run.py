"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--scale small`` (the default)
finishes in a few minutes and exercises every harness; ``--scale paper``
runs the paper-scale sweeps (tens of minutes of partitioning — the flat-CSR
refinement engine makes these feasible in-container).  ``--full`` is kept as
an alias for ``--scale paper``.
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import bench_amg, bench_bounds, bench_exec, bench_kernels, bench_lp
from benchmarks import bench_mcl, bench_partition, bench_plan_build, bench_select
from benchmarks import bench_serve, bench_tab2, bench_versus, roofline
from benchmarks.common import csv_lines
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "tab2": bench_tab2.run,
    "amg": bench_amg.run,
    "lp": bench_lp.run,
    "mcl": bench_mcl.run,
    "bounds": bench_bounds.run,
    "kernels": bench_kernels.run,
    "plan": bench_plan_build.run,
    "partition": bench_partition.run,
    "select": bench_select.run,
    "versus": bench_versus.run,
    "exec": bench_exec.run,
    "serve": bench_serve.run,
    "roofline": roofline.run,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--scale",
        default=None,
        choices=("small", "paper"),
        help="instance sizes: 'small' keeps the container default fast, "
        "'paper' runs the paper-scale sweep",
    )
    ap.add_argument(
        "--full", action="store_true", help="alias for --scale paper (kept for CI)"
    )
    ap.add_argument(
        "--quick", action="store_true", help="alias for --scale small (CI smoke)"
    )
    ap.add_argument("--only", default=None, choices=list(SUITES))
    ap.add_argument("--out", default="experiments/paper")
    args = ap.parse_args(argv)
    if args.quick and (args.full or args.scale == "paper"):
        ap.error("--quick conflicts with --full/--scale paper")
    scale = args.scale or ("paper" if args.full else "small")
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        try:
            if name == "roofline":
                records = fn(out_dir="experiments")
            else:
                records = fn(out_dir=args.out, quick=scale == "small")
        except Exception as e:  # a suite failing should not hide the others
            print(f"{name},-1,ERROR:{type(e).__name__}:{e}")
            failures += 1
            continue
        for line in csv_lines(records):
            print(line)
        sys.stdout.flush()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
