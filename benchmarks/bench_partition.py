"""Partitioner engine benchmark: flat vs loop vs device engines.

Cells (each instance × engine):
- ``partition/flat`` and ``partition/loop``: end-to-end ``partition()`` wall
  time and final connectivity on the bench instances.  The acceptance cell
  is the 10k-row ER instance at p=16 (``--full``): the flat engine must be
  >= 8x faster than the loop-FM reference at connectivity within 5% (or
  better) and identical balance feasibility.  The quick/smoke grid runs the
  same comparison at reduced size so CI exercises the claim on every PR.
- ``partition/device`` vs ``partition/flat_x{S}``: the device-engine
  multi-start acceptance cell.  One batched ``engine="device"`` call (all S
  seeds refined side by side on device, steady-state — the first call's
  jit compile is warmed up out of band and amortizes across same-bucket
  planning calls) against the flat engine's best-of-S sequential seeds,
  which is the host idiom it replaces.  ``--full`` asserts >= 5x end-to-end
  with connectivity within 5%.
- ``partition/device_coarsen`` vs ``partition/host_coarsen``: the
  device-resident V-cycle acceptance cell.  Both sides are the same
  ``engine="device"`` call; only the descend differs (``coarsen="auto"``
  keeps coarsening on device, ``coarsen="host"`` forces the retained scipy
  descend).  ``--full`` asserts >= 3x end-to-end with connectivity within
  5%.  Device records carry phase-split columns (``coarsen_s`` /
  ``refine_s`` / ``polish_s`` seconds at the best-timed rep).
- a small structured cell (27-pt stencil rowwise model) so quality is
  checked on mesh-like inputs, not just ER.

Every record carries ``engine`` and ``pins_per_sec`` (hypergraph pins
planned per wall-second — the partition-throughput headline that
``check_regression.py`` gates against ``partition_smoke.json``).

Timing is interleaved best-of-``repeats`` per engine (both sides measured
under the same host conditions, so machine noise cannot tilt the ratio).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import SpGEMMInstance, build_model, evaluate, partition
from repro.core.matrices import stencil27
from repro.sparse.structure import random_structure

ACCEPT_SPEEDUP = 8.0
ACCEPT_CONN = 1.05
DEVICE_ACCEPT_SPEEDUP = 5.0  # device call vs flat best-of-S multi-start
DEVICE_ACCEPT_CONN = 1.05
DEVICE_BENCH_STARTS = 8  # seeds in the multi-start comparison
COARSEN_ACCEPT_SPEEDUP = 3.0  # device-resident V-cycle vs host-coarsen descend
COARSEN_ACCEPT_CONN = 1.05


def _er_instance(rows: int, seed: int = 0) -> SpGEMMInstance:
    rng = np.random.default_rng(seed)
    k = rows // 2
    return SpGEMMInstance(
        random_structure(rows, k, 8.0 / k, rng),
        random_structure(k, k, 8.0 / k, rng),
        name=f"er{rows//1000}k" if rows >= 1000 else f"er{rows}",
    )


def _cell(hg, p: int, name: str, repeats: int = 2, eps: float = 0.10) -> list[dict]:
    # interleaved best-of-``repeats`` per engine, so host-level timing noise
    # hits both sides of the comparison alike
    best = {"flat": float("inf"), "loop": float("inf")}
    res = {}
    for _rep in range(repeats):
        for engine in ("flat", "loop"):
            t0 = time.perf_counter()
            res[engine] = partition(hg, p, eps=eps, seed=0, engine=engine)
            best[engine] = min(best[engine], time.perf_counter() - t0)
    results = {}
    for engine in ("flat", "loop"):
        costs = evaluate(hg, res[engine].parts, p)
        results[engine] = (best[engine], res[engine].connectivity, costs.comp_imbalance)
    t_flat, c_flat, i_flat = results["flat"]
    t_loop, c_loop, i_loop = results["loop"]
    speedup = t_loop / max(t_flat, 1e-9)
    conn_ratio = c_flat / max(c_loop, 1)
    # identical balance feasibility: both inside the eps cap (+ rounding) or
    # both forced over it by heavy vertices
    feas_flat, feas_loop = i_flat <= eps + 0.03, i_loop <= eps + 0.03
    recs = []
    for engine in ("flat", "loop"):
        t, c, imb = results[engine]
        recs.append(
            {
                "name": f"{name}/partition/{engine}/p{p}",
                "status": "ok",
                "engine": engine,
                "us_per_call": int(t * 1e6),
                "n_vertices": hg.n_vertices,
                "n_nets": hg.n_nets,
                "n_pins": hg.n_pins,
                "pins_per_sec": int(hg.n_pins / max(t, 1e-9)),
                "connectivity": int(c),
                "comp_imbalance": round(float(imb), 4),
                "speedup_vs_loop": round(speedup, 1),
                "conn_vs_loop": round(conn_ratio, 3),
                "balance_feasibility_identical": bool(feas_flat == feas_loop),
            }
        )
    return recs


def _device_cell(
    hg,
    p: int,
    name: str,
    repeats: int = 2,
    eps: float = 0.10,
    starts: int = DEVICE_BENCH_STARTS,
) -> list[dict]:
    """Multi-start acceptance cell: one batched ``engine="device"`` call vs
    the flat engine's best-of-``starts`` sequential seeds (the host
    multi-start idiom the device batch replaces)."""
    partition(hg, p, eps=eps, seed=0, engine="device")  # warm the jit cache
    best = {"device": float("inf"), "flat": float("inf")}
    res = {}
    for _rep in range(repeats):
        t0 = time.perf_counter()
        res["device"] = partition(hg, p, eps=eps, seed=0, engine="device")
        best["device"] = min(best["device"], time.perf_counter() - t0)
        t0 = time.perf_counter()
        winner = None
        for s in range(starts):
            cand = partition(hg, p, eps=eps, seed=s, engine="flat")
            if winner is None or cand.connectivity < winner.connectivity:
                winner = cand
        res["flat"] = winner
        best["flat"] = min(best["flat"], time.perf_counter() - t0)
    speedup = best["flat"] / max(best["device"], 1e-9)
    conn_ratio = res["device"].connectivity / max(res["flat"].connectivity, 1)
    recs = []
    for engine, label in (("device", "device"), ("flat", f"flat_x{starts}")):
        t = best[engine]
        imb = evaluate(hg, res[engine].parts, p).comp_imbalance
        rec = {
            "name": f"{name}/partition/{label}/p{p}",
            "status": "ok",
            "engine": engine,
            "multi_starts": starts,
            "us_per_call": int(t * 1e6),
            "n_vertices": hg.n_vertices,
            "n_nets": hg.n_nets,
            "n_pins": hg.n_pins,
            "pins_per_sec": int(hg.n_pins / max(t, 1e-9)),
            "connectivity": int(res[engine].connectivity),
            "comp_imbalance": round(float(imb), 4),
            "speedup_vs_flat_multistart": round(speedup, 2),
            "conn_vs_flat_multistart": round(conn_ratio, 3),
        }
        rec.update(_phase_cols(res[engine]))
        recs.append(rec)
    return recs


def _phase_cols(res) -> dict:
    """Phase-split columns for device-engine records: seconds spent in the
    descend (``coarsen_s``), the batched device refinement (``refine_s``)
    and the host K-way polish (``polish_s``).  Host engines carry no phase
    breakdown and get no columns."""
    phases = getattr(res, "phases", None)
    if not phases:
        return {}
    return {k: round(float(v), 4) for k, v in sorted(phases.items())}


def _coarsen_cell(
    hg, p: int, name: str, repeats: int = 3, eps: float = 0.10
) -> list[dict]:
    """Device-resident coarsening acceptance cell: the same
    ``engine="device"`` call with the descend on device
    (``coarsen="auto"``) against forced host coarsening
    (``coarsen="host"``, the retained scipy descend).  Both sides share the
    batched refinement and host polish, so the column isolates what keeping
    the V-cycle on device buys end to end."""
    for mode in ("auto", "host"):  # warm both jit cache paths
        partition(hg, p, eps=eps, seed=0, engine="device", coarsen=mode)
    best = {"auto": float("inf"), "host": float("inf")}
    res = {}
    phases = {}
    for _rep in range(repeats):
        for mode in ("auto", "host"):
            t0 = time.perf_counter()
            r = partition(hg, p, eps=eps, seed=0, engine="device", coarsen=mode)
            dt = time.perf_counter() - t0
            if dt < best[mode]:
                best[mode] = dt
                phases[mode] = _phase_cols(r)
            res[mode] = r
    speedup = best["host"] / max(best["auto"], 1e-9)
    conn_ratio = res["auto"].connectivity / max(res["host"].connectivity, 1)
    recs = []
    for mode, label in (("auto", "device_coarsen"), ("host", "host_coarsen")):
        t = best[mode]
        imb = evaluate(hg, res[mode].parts, p).comp_imbalance
        rec = {
            "name": f"{name}/partition/{label}/p{p}",
            "status": "ok",
            "engine": "device",
            "coarsen": mode,
            "us_per_call": int(t * 1e6),
            "n_vertices": hg.n_vertices,
            "n_nets": hg.n_nets,
            "n_pins": hg.n_pins,
            "pins_per_sec": int(hg.n_pins / max(t, 1e-9)),
            "connectivity": int(res[mode].connectivity),
            "comp_imbalance": round(float(imb), 4),
            "speedup_vs_host_coarsen": round(speedup, 2),
            "conn_vs_host_coarsen": round(conn_ratio, 3),
        }
        rec.update(phases[mode])
        recs.append(rec)
    return recs


def run(out_dir: str | None = None, quick: bool = True) -> list[dict]:
    records = []
    if quick:
        # 5k rows keeps CI fast but stays on the engines' V-cycle speed
        # path (instances <= SMALL_DIRECT take the multi-start quality path,
        # which deliberately spends the speedup on connectivity instead)
        er = build_model(_er_instance(5_000), "rowwise")
        records += _cell(er, 16, "er5k")
    else:
        # the acceptance instance: 10k rows, p=16
        er = build_model(_er_instance(10_000), "rowwise")
        records += _cell(er, 16, "er10k")
    # small structured quality cell — runs the multi-start quality path, so
    # the interesting column is conn_vs_loop, not the speedup
    a = stencil27(7)
    records += _cell(
        build_model(SpGEMMInstance(a, a, name="stencil7"), "rowwise"), 4, "stencil7"
    )
    # device multi-start throughput cell on the same ER instance (skipped
    # gracefully where jax is absent: the driver falls back to flat and the
    # comparison would be flat-vs-flat noise)
    try:
        import repro.core.refine_device  # noqa: F401
    except ImportError:
        pass
    else:
        name = "er5k" if quick else "er10k"
        records += _device_cell(er, 16, name)
        # device-resident coarsening cell: device vs host descend inside the
        # same engine="device" call (the V-cycle residency acceptance)
        records += _coarsen_cell(er, 16, name)
    if not quick:
        rec = records[0]
        assert rec["balance_feasibility_identical"], "balance feasibility diverged"
        assert rec["speedup_vs_loop"] >= ACCEPT_SPEEDUP, (
            f"flat engine only {rec['speedup_vs_loop']}x faster on er10k "
            f"(acceptance: >= {ACCEPT_SPEEDUP}x)"
        )
        assert rec["conn_vs_loop"] <= ACCEPT_CONN, (
            f"flat connectivity {rec['conn_vs_loop']}x the loop reference "
            f"(acceptance: <= {ACCEPT_CONN})"
        )
        dev = [r for r in records if r.get("engine") == "device"]
        assert dev, "device acceptance cell missing (jax unavailable?)"
        assert dev[0]["speedup_vs_flat_multistart"] >= DEVICE_ACCEPT_SPEEDUP, (
            f"device engine only {dev[0]['speedup_vs_flat_multistart']}x the "
            f"flat multi-start on er10k (acceptance: >= {DEVICE_ACCEPT_SPEEDUP}x)"
        )
        assert dev[0]["conn_vs_flat_multistart"] <= DEVICE_ACCEPT_CONN, (
            f"device connectivity {dev[0]['conn_vs_flat_multistart']}x the "
            f"flat multi-start winner (acceptance: <= {DEVICE_ACCEPT_CONN})"
        )
        resident = [r for r in records if r.get("coarsen") == "auto"]
        assert resident, "device-coarsening acceptance cell missing"
        assert resident[0]["speedup_vs_host_coarsen"] >= COARSEN_ACCEPT_SPEEDUP, (
            f"device-resident coarsening only "
            f"{resident[0]['speedup_vs_host_coarsen']}x the host-coarsen "
            f"descend on er10k (acceptance: >= {COARSEN_ACCEPT_SPEEDUP}x)"
        )
        assert resident[0]["conn_vs_host_coarsen"] <= COARSEN_ACCEPT_CONN, (
            f"device-resident connectivity "
            f"{resident[0]['conn_vs_host_coarsen']}x the host-coarsen result "
            f"(acceptance: <= {COARSEN_ACCEPT_CONN})"
        )
    if out_dir and not quick:
        # only the full acceptance run refreshes the committed artifact;
        # smoke runs print without clobbering the 10k measurement
        from benchmarks.common import emit

        emit(records, out_dir, "partition.json")
    return records


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true", help="10k-row acceptance run")
    mode.add_argument(
        "--smoke", action="store_true", help="reduced-size CI run (the default)"
    )
    ap.add_argument("--out", default=None, help="artifact dir, e.g. experiments/paper")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run(out_dir=args.out, quick=not args.full):
        print(r)
