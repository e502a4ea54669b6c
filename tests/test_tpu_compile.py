"""Compile the main path's kernels and executor steps for a described TPU.

Nothing runs: the TPU compiler that ships with jax compiles for a v5e 2x2
topology that is described, not attached, so the Mosaic lowering of the
kernels (SMEM and VMEM limits, tiling) and the sharded executor steps are
checked on every run of the suite.  The topology is described inside a
fixture, never at import, so that only the test worker that runs this file
loads the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import repro
from repro.core.matrices import amg_instances
from repro.kernels import bsr_spgemm as bsr_spgemm_mod
from repro.kernels.bsr_spmm import bsr_spmm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not describable here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "block, n_pairs", [(1, 2 * bsr_spgemm_mod.PAIRS_PER_CALL + 5), (128, 600)]
)
def test_bsr_spgemm_compiles_for_tpu(one_chip, block, n_pairs):
    """Mosaic accepts the pair-list kernel; at block 1 the list is longer
    than one call's SMEM share, so the chunk loop compiles too."""
    n_a, n_c = 4 * n_pairs // 3, n_pairs // 2
    blocks = _sds((n_a, block, block), jnp.float32, one_chip)
    pairs = _sds((n_pairs,), jnp.int32, one_chip)
    compiled = bsr_spgemm_mod._bsr_spgemm_jit.lower(
        blocks, blocks, pairs, pairs, pairs, n_c_blocks=n_c, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bsr_spmm_compiles_for_tpu(one_chip):
    nb, b = 64, 128
    compiled = bsr_spmm.lower(
        _sds((nb, b, b), jnp.float32, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((16 * b, 2 * b), jnp.float32, one_chip),
        m_blocks=16,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def amg_small():
    inst, _ = amg_instances(6)
    return inst


def _step_compiled(step_and_tables, mesh, axes, a_shape, b_shape):
    step, tables = step_and_tables
    sharding = NamedSharding(mesh, P(axes))
    return (
        jax.jit(step)
        .lower(
            _sds(a_shape, jnp.float32, sharding),
            _sds(b_shape, jnp.float32, sharding),
            *(jax.ShapeDtypeStruct(np.shape(t), jnp.int32) for t in tables),
        )
        .compile()
    )


def test_fine_step_compiles_for_four_tpus(topo, amg_small):
    from repro.distributed.spgemm_exec import make_fine_step

    plan = repro.plan(amg_small, p=4, model="fine").execution_plan
    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    compiled = _step_compiled(
        make_fine_step(plan, mesh),
        mesh,
        "x",
        (4, plan.local_ids["a_nz"].shape[1]),
        (4, plan.local_ids["b_nz"].shape[1]),
    )
    assert "all-to-all" in compiled.as_text()


def test_monoC_pallas_step_compiles_for_four_tpus(topo, amg_small):
    from repro.distributed.spgemm_exec import make_monoC_step

    plan = repro.plan(amg_small, p=4, model="monoC").execution_plan
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("x", "y"))
    step = make_monoC_step(plan, mesh, block=1, backend="pallas", axes=("x", "y"))
    compiled = _step_compiled(
        step,
        mesh,
        ("x", "y"),
        (4, plan.local_ids["a_nz"].shape[1], 1, 1),
        (4, plan.local_ids["b_nz"].shape[1], 1, 1),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text


def test_blocked_fine_step_compiles_for_four_tpus(topo, amg_small):
    """Items-major tables of 3x3 and 3x6 blocks, the routes shipping whole
    columns."""
    from repro.distributed.spgemm_exec import make_fine_step

    plan = repro.plan(amg_small, p=4, model="fine", blocks=((3, 3), (3, 6))).execution_plan
    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    compiled = _step_compiled(
        make_fine_step(plan, mesh),
        mesh,
        "x",
        (4, 9, plan.local_ids["a_nz"].shape[1]),
        (4, 18, plan.local_ids["b_nz"].shape[1]),
    )
    assert "all-to-all" in compiled.as_text()


@pytest.fixture(scope="module")
def elastic72(one_chip):
    """The 72^3 elasticity cell's whole program (layout change, value
    scatter, step) at its real shapes, compiled for one v5e.  Only the
    shapes of the p=1 plan's tables and its run structure matter, so the
    plan is built from them: each of the n_c produced slots a run of 9 to
    27 block products (the 27-point stencil's longest is 27, so the step
    makes 5 passes), folding into the owned C slot of the same rank."""
    from types import SimpleNamespace

    from repro.distributed.plan_ir import FinePlan, Route
    from repro.distributed.registry import _fine_runner

    n_a, n_b, n_c, n_mult = 9800344, 1643032, 4410944, 43614208
    runs = np.full(n_c, 9)
    extra, rest = divmod(n_mult - runs.sum(), 18)
    runs[:extra] += 18
    runs[extra] += rest
    pad = np.full((1, 1, 1), -1)
    plan = FinePlan(
        model="fine",
        p=1,
        ownership={"a_nz": np.zeros(n_a), "b_nz": np.zeros(n_b)},
        local_ids={"a_nz": np.arange(n_a)[None], "b_nz": np.arange(n_b)[None],
                   "c_nz": np.zeros((1, n_c)), "c_prod": np.zeros((1, n_c))},
        routes={r: Route("A", pad, pad, 0, 0) for r in ("expand_a", "expand_b", "reduce_c")},
        compute={"pair_a": np.zeros((1, n_mult)), "pair_b": np.zeros((1, n_mult)),
                 "pair_c": np.repeat(np.arange(n_c), runs)[None], "reduce_recv_slot": pad,
                 "prod_to_owned": np.arange(n_c)[None]},
        blocks=((3, 3), (3, 6)),
    )
    assert plan.segment_passes == 5
    mesh = Mesh(np.array(list(one_chip.device_set)), ("x",))
    setup = _fine_runner(plan, SimpleNamespace(nnz=n_a, shape=(1, 1)),
                         SimpleNamespace(nnz=n_b, shape=(1, 1)), mesh, dtype=np.float32,
                         block=1, backend=None, axis="x", axes=("x", "y"))
    return jax.jit(setup.run).lower(
        _sds(setup.a_shape, jnp.float32, one_chip),
        _sds(setup.b_shape, jnp.float32, one_chip),
        *(_sds(np.shape(t), jnp.int32, one_chip) for t in setup.tables),
    ).compile()


def test_blocked_fine_runner_fits_one_chip_at_72(elastic72):
    """The elasticity cell's program fits one v5e's 16 GiB."""
    mem = elastic72.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert total < 14 * 2**30, total


def test_segment_sum_reads_no_view_of_a_buffer_it_overwrites(elastic72):
    """No fusion of local compute takes both a buffer and a bitcast view of
    it.  A fusion may write its result over a buffer it reads elementwise,
    and a view read at another index would then see values the fusion has
    already overwritten: a shift-and-add pass that reads behind through a
    prefix slice compiles to that, and summed wrong on a v5e."""
    instr = re.compile(r"^\s*(?:ROOT )?%(\S+) = \S+ ([a-z][\w\-]*)\(([^)]*)\)")
    text = elastic72.as_text()
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    ops = {}
    for line in lines[start + 1:]:
        m = instr.match(line)
        if m:
            args = [a.strip().lstrip("%") for a in m[3].split(",") if a.strip()]
            ops[m[1]] = (m[2], args, "repro.local" in line)
    fusions = [(n, args) for n, (op, args, local) in ops.items() if op == "fusion" and local]
    assert fusions
    viewing = {
        name: arg for name, args in fusions for arg in args
        if ops.get(arg, ("",))[0] == "bitcast" and ops[arg][1][0] in args
    }
    assert not viewing, viewing
