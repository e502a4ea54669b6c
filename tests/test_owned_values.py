"""The owned operand tables are read out of the value vectors through the
plan's id lists (``spgemm_exec.owned_values``).

Held, bit for bit, to the tables the value scatter built before (a zero
table written through the inverted id list, ``scatter_tables`` below), and
the executor's C to the same step run over those tables, bit for bit, and
to scipy's float64 product.  At p = 1 in this process; at p = 3 and 4 on
host devices in a child process (the device count is fixed before jax is
imported):

    python tests/test_owned_values.py <p>   # prints the child's JSON
"""
from __future__ import annotations

import copy
import functools
import json
import os
import subprocess
import sys
import traceback
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
TOL = 1e-5
SQUARE2 = ((2, 2), (2, 2))
ELASTIC = ((3, 3), (3, 6))

#: name -> (model, blocks of the fine family, monoC's block, batch, empty part)
CASES = {
    "fine": ("fine", None, 1, None, False),
    "fine-2x2": ("fine", SQUARE2, 1, None, False),
    "fine-3x3.3x6": ("fine", ELASTIC, 1, None, False),
    "monoB-3x3.3x6": ("monoB", ELASTIC, 1, None, False),
    "columnwise": ("columnwise", None, 1, None, False),
    "monoC-b1": ("monoC", None, 1, None, False),
    "monoC-b2": ("monoC", None, 2, None, False),
    "summa2d-b2": ("summa2d", None, 2, None, False),
    "fine-empty-part": ("fine", None, 1, None, True),
    "fine-3x3.3x6-empty-part": ("fine", ELASTIC, 1, None, True),
    "fine-batch2": ("fine", None, 1, 2, False),
    "fine-3x3.3x6-batch2": ("fine", ELASTIC, 1, 2, False),
    "monoC-b2-batch2": ("monoC", None, 2, 2, False),
}


def structures(seed: int):
    from repro.sparse.structure import random_structure

    rng = np.random.default_rng(seed)
    return random_structure(14, 11, 0.3, rng), random_structure(11, 13, 0.3, rng)


def plan_for(model, a_s, b_s, p, blocks, empty_part):
    """The planned executor's plan, or for ``empty_part`` a fine plan whose
    A nonzeros all sit on device 0 and B's on the last device, so the other
    devices own no A (and no B) nonzero and their padded rows read 0."""
    import repro
    from repro.core.spgemm_models import SpGEMMInstance
    from repro.distributed.plan_ir import SCALAR_BLOCKS, build_fine_plan

    if not empty_part:
        kw = {} if blocks is None else {"blocks": blocks}
        return repro.plan(a_s, b_s, p=p, model=model, **kw).execution_plan
    inst = SpGEMMInstance(a_s, b_s)
    mult = np.arange(inst.n_mult) % p
    return build_fine_plan(
        inst, mult, p, a_part=np.zeros(a_s.nnz, np.int64),
        b_part=np.full(b_s.nnz, p - 1, np.int64), blocks=blocks or SCALAR_BLOCKS,
    )


def step_of(model, plan, mesh, block):
    """The model's step over owned tables, as its runner builds it."""
    from repro.distributed.spgemm_exec import make_fine_step, make_monoC_step
    from repro.distributed.summa import make_summa_step

    if model == "monoC":
        return make_monoC_step(plan, mesh, block=block, backend="xla", axes=("x", "y"))
    if model == "summa2d":
        return make_summa_step(plan, mesh, block=block, backend="xla", axes=("x", "y"))
    return make_fine_step(plan, mesh, axis="x")


def scatter_tables(plan, a_vals, b_vals, columns):
    """The owned tables as the value scatter built them: a zero table
    written at each id's (device, slot) from ``registry.owner_slot``.
    ``columns``: the vectors are items-major (items, n)."""
    from repro.distributed.registry import owner_slot

    out = []
    for key, vals in (("a_nz", a_vals), ("b_nz", b_vals)):
        ids = plan.local_ids[key]
        n = vals.shape[-1] if columns else vals.shape[0]
        dev, slot = owner_slot(ids, n)
        if columns:
            tab = np.zeros((plan.p, vals.shape[0], ids.shape[1]), vals.dtype)
            tab[dev, :, slot] = vals.T
        else:
            tab = np.zeros((plan.p, ids.shape[1], *vals.shape[1:]), vals.dtype)
            tab[dev, slot] = vals
        out.append(tab)
    return out


def scipy_c(a_s, b_s, c_s, a_vals, b_vals):
    """C's (nnz_c, r, c) blocks of the float64 product, and of |A||B|."""
    import scipy.sparse as sp

    def expand(s, vals):
        csr = s.csr
        shape = (csr.shape[0] * vals.shape[1], csr.shape[1] * vals.shape[2])
        return sp.bsr_matrix(
            (np.asarray(vals, np.float64), csr.indices, csr.indptr), shape=shape
        ).tocsr()

    r, c = a_vals.shape[1], b_vals.shape[2]
    rows, cols = c_s.coo()

    def at_c(m):
        dense = m.toarray().reshape(c_s.shape[0], r, c_s.shape[1], c)
        return dense[rows, :, cols, :]

    a64, b64 = expand(a_s, a_vals), expand(b_s, b_vals)
    return at_c(a64 @ b64), at_c(abs(a64) @ abs(b64))


def same_bits(x, y) -> bool:
    x, y = np.asarray(x), np.asarray(y)
    return x.shape == y.shape and np.array_equal(x.view(np.int32), y.view(np.int32))


def check_case(p: int, name: str) -> list[str]:
    """Run one case at ``p`` devices; returns the ways the executor built
    its owned tables, and raises where a table or C differs."""
    import jax

    from repro.core.spgemm_models import SpGEMMInstance
    from repro.distributed.plan_ir import SCALAR_BLOCKS
    from repro.distributed.registry import get_spec
    from repro.distributed.runtime import compile_spgemm
    from repro.distributed.spgemm_exec import owned_c_values, owned_values

    model, blocks, block, batch, empty_part = CASES[name]
    a_s, b_s = structures(sum(map(ord, name)))
    plan = plan_for(model, a_s, b_s, p, blocks, empty_part)
    spec = get_spec(model)
    mesh = spec.default_mesh(p, instance=SpGEMMInstance(a_s, b_s))
    axis = ("x", "y") if model in ("monoC", "summa2d") else "x"
    (r, k), (_, c) = blocks or ((block, block), (block, block))
    columns = plan.blocks != SCALAR_BLOCKS
    rng = np.random.default_rng(p)
    n_sets = batch or 1
    a_sets = rng.standard_normal((n_sets, a_s.nnz, r, k), dtype=np.float32)
    b_sets = rng.standard_normal((n_sets, b_s.nnz, k, c), dtype=np.float32)

    def executor_layout(vals):
        # what the runner's value argument holds: (nnz,) scalars, flat
        # block-major blocks for the fine family, (nnz, b, b) for monoC
        if columns:
            return vals.reshape(-1)
        return vals.reshape(vals.shape[0]) if model not in ("monoC", "summa2d") else vals

    def owned_input(vals):
        # what owned_values reads: items-major (items, nnz) for blocks
        return vals.reshape(vals.shape[0], -1).T if columns else executor_layout(vals)

    # the owned tables, bit for bit
    ways, id_tables, own = owned_values(plan, a_s.nnz, b_s.nnz, mesh, axis, columns=columns)
    got = jax.jit(lambda a, b, *t: own(a, b, *t)[:2])(
        owned_input(a_sets[0]), owned_input(b_sets[0]), *(np.int32(t) for t in id_tables)
    )
    want = scatter_tables(plan, owned_input(a_sets[0]), owned_input(b_sets[0]), columns)
    for g, w, which in zip(got, want, "AB"):
        assert same_bits(g, w), f"{which}'s owned table differs from the scatter's"
    if empty_part:
        assert not want[0][1:].any() and not want[1][:-1].any()

    # C: the executor against the step over the scatter's tables (mapped
    # over the value sets where the executor is batched, as the batched
    # runner maps it), and scipy
    step, step_tables = step_of(model, plan, mesh, block)
    exe = compile_spgemm(
        plan, a_s, b_s, mesh, block=block, backend="xla", axes=("x", "y"), batch=batch,
        cache=False,
    )
    a_in, b_in = (np.stack([executor_layout(v) for v in sets]) for sets in (a_sets, b_sets))
    tabs = [np.stack(t) for t in zip(*(
        scatter_tables(plan, owned_input(a), owned_input(b), columns)
        for a, b in zip(a_sets, b_sets)
    ))]
    step_tables = [np.int32(t) for t in step_tables]
    if batch:
        got_c = exe(a_in, b_in)
        parent = jax.vmap(step, in_axes=(0, 0) + (None,) * len(step_tables))
    else:
        got_c = exe(a_in[0], b_in[0])[None]
        parent = lambda a, b, *t: step(a[0], b[0], *t)[None]  # noqa: E731
    parent_c = jax.jit(parent)(*tabs, *step_tables)
    if columns:
        parent_c = parent_c.transpose(0, 1, 3, 2).reshape(n_sets, -1)
    assert same_bits(got_c, parent_c), "C differs from the scatter's"
    c_s = SpGEMMInstance(a_s, b_s).c
    for i in range(n_sets):
        vals = owned_c_values(got_c[i], plan).reshape(-1, r, c)
        want_c, scale = scipy_c(a_s, b_s, c_s, a_sets[i], b_sets[i])
        assert (np.abs(vals - want_c) <= TOL * (1 + scale)).all(), f"C of value set {i}"
    return list(ways)


def run_cases(p: int) -> dict:
    out = {}
    for name in CASES:
        try:
            out[name] = {"ways": check_case(p, name)}
        except Exception:  # noqa: BLE001 — reported to the parent test
            out[name] = {"error": traceback.format_exc(limit=4)}
    return out


@functools.lru_cache(maxsize=None)
def results(p: int) -> dict:
    if p == 1:
        return run_cases(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={p}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, __file__, str(p)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("p", [1, 3, 4])
@pytest.mark.parametrize("case", list(CASES))
def test_owned_tables_and_c_match_the_scatter(p, case):
    got = results(p)[case]
    assert "error" not in got, got["error"]
    # a planned p=1 list is the caller's order; at p>1 every device gathers
    assert got["ways"] == ["in_place" if p == 1 else "gathered"] * 2


@pytest.mark.parametrize("model, blocks", [
    ("fine", None), ("fine", ELASTIC), ("monoB", ELASTIC), ("monoC", None),
    ("summa2d", None), ("columnwise", None), ("outer", None),
])
def test_planned_one_device_executor_moves_no_value(model, blocks):
    """A p=1 plan builds both owned tables in place: the counters say so,
    and the program has no gather and no scatter under the scope."""
    import repro
    from repro.distributed import runtime
    from test_named_scopes import ops_by_scope

    a_s, b_s = structures(5)
    kw = {} if blocks is None else {"blocks": blocks}
    runtime.cache_clear()
    exe = repro.plan(a_s, b_s, p=1, model=model, **kw).compile(dtype=np.float32)
    info = runtime.cache_info()
    assert (info["values_in_place"], info["values_gathered"]) == (2, 0), info
    moved = ops_by_scope(exe.runtime._compiled.as_text(), ("gather", "scatter"))
    assert "repro.scatter_values" not in moved, moved
    runtime.cache_clear()
    assert (runtime.cache_info()["values_in_place"], runtime.cache_info()["values_gathered"]) == (0, 0)


@pytest.mark.parametrize("blocks", [None, ELASTIC], ids=["scalar", "3x3.3x6"])
def test_one_device_plan_with_permuted_ids_is_gathered(blocks):
    """A hand-built p=1 plan whose A id list is a permutation other than
    the identity: A is gathered, B stays in place, and C is right."""
    import jax
    from jax.sharding import Mesh

    from repro.core.spgemm_models import SpGEMMInstance
    from repro.distributed import runtime
    from repro.distributed.plan_ir import SCALAR_BLOCKS, build_fine_plan
    from repro.distributed.spgemm_exec import make_fine_step, owned_c_values
    from test_named_scopes import ops_by_scope

    a_s, b_s = structures(9)
    inst = SpGEMMInstance(a_s, b_s)
    blocks = blocks or SCALAR_BLOCKS
    base = build_fine_plan(inst, np.zeros(inst.n_mult, np.int64), 1, blocks=blocks)
    plan = copy.deepcopy(base)
    n = a_s.nnz
    perm = np.random.default_rng(3).permutation(n)
    # slot s now holds id perm[s]: a multiplication that read id g's slot g
    # reads slot inv[g]; the slots past the owned ones keep their meaning
    inv = np.argsort(perm)
    pa = plan.compute["pair_a"]
    plan.local_ids["a_nz"] = perm[None].astype(base.local_ids["a_nz"].dtype)
    plan.compute["pair_a"] = np.where(pa < n, inv[np.minimum(pa, n - 1)], pa)

    (r, k), (_, c) = blocks
    rng = np.random.default_rng(8)
    a_vals = rng.standard_normal((n, r, k), dtype=np.float32)
    b_vals = rng.standard_normal((b_s.nnz, k, c), dtype=np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    runtime.cache_clear()
    exe = runtime.compile_spgemm(plan, a_s, b_s, mesh)
    info = runtime.cache_info()
    assert (info["values_in_place"], info["values_gathered"]) == (1, 1), info
    hlo = exe._compiled.as_text()
    assert ops_by_scope(hlo, ("gather",)).get("repro.scatter_values", 0) > 0
    assert "repro.scatter_values" not in ops_by_scope(hlo, ("scatter",))
    got = exe(a_vals.reshape(-1), b_vals.reshape(-1))
    # the same products summed in the same order as the identity plan's
    base_exe = runtime.compile_spgemm(base, a_s, b_s, mesh)
    assert same_bits(got, base_exe(a_vals.reshape(-1), b_vals.reshape(-1)))
    columns = blocks != SCALAR_BLOCKS
    ins = [v.reshape(v.shape[0], -1).T if columns else v.reshape(-1) for v in (a_vals, b_vals)]
    step, step_tables = make_fine_step(plan, mesh)
    parent = jax.jit(step)(*scatter_tables(plan, *ins, columns),
                           *(np.int32(t) for t in step_tables))
    if columns:
        parent = parent.transpose(0, 2, 1).reshape(-1)
    assert same_bits(got, parent)
    want, scale = scipy_c(a_s, b_s, inst.c, a_vals, b_vals)
    vals = owned_c_values(got, plan).reshape(-1, r, c)
    assert (np.abs(vals - want) <= TOL * (1 + scale)).all()


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    print(json.dumps(run_cases(int(sys.argv[1]))))
