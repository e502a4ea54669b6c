"""The benchmark's copy of the structure generator gives exactly what the
program's own generator gives, the configurations state the sizes it
builds, and the plan store is keyed by the program's sources."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest
from conftest import ROOT

from benchmarks.chip import harness

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


def generator(name):
    return harness.load_module(ROOT / "benchmarks" / "chip" / "generators" / f"{name}.py")


def same(mine, theirs):
    return (mine.shape == theirs.shape and np.array_equal(mine.indptr, theirs.indptr)
            and np.array_equal(mine.indices, theirs.indices))


@pytest.mark.parametrize("n", [6, 9, 12, 15])
def test_amg_structures_match_the_program(n):
    from repro.core.matrices import amg_instances

    inst, _ = amg_instances(n)
    a, p = generator("amg27").structures({"grid": n, "aggregate": 3, "smoothing_degree": 1})
    assert same(a, inst.a.csr) and same(p, inst.b.csr)


@pytest.mark.parametrize("name", ["amg27-ap-n72-fine-p1", "amg27-ap-n72-monoC-p4"])
def test_configurations_state_the_published_grid_and_their_cut(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    assert cfg["published"]["grid"] == 104 and cfg["grid"] == 72
    assert (104 / 72) ** 3 == pytest.approx(3.0, abs=0.02)
    n, agg = cfg["grid"], cfg["aggregate"]
    assert cfg["sizes"]["rows"] == n**3
    assert n % agg == 0


def copy_program(dest):
    shutil.copytree(ROOT / "src" / "repro", dest / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return harness.Bench(dest, {})


def test_two_program_hashes_never_share_a_plan(tmp_path):
    import repro
    from repro.core.matrices import amg_instances

    one = copy_program(tmp_path / "one")
    two = copy_program(tmp_path / "two")
    assert one.program_hash() == two.program_hash()
    with open(two.root / "src" / "repro" / "api.py", "a") as f:
        f.write("\n# changed\n")
    assert one.program_hash() != two.program_hash()
    assert one.store_dir() != two.store_dir()

    inst, _ = amg_instances(6)

    def events(bench):
        sess = repro.session(p=1, model="fine", store_dir=str(bench.store_dir()))
        sess.entry_for(inst.a, inst.b)
        return [e.kind for e in sess.events]

    assert "saved" in events(one)
    assert events(one)[0] == "restored"
    assert events(two)[0] == "cold_replan"
