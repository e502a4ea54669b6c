"""Subprocess runner for multi-device tests.

Run as:  python tests/multidev_runner.py <case>
Sets XLA host-device-count BEFORE importing jax (must not leak into the main
pytest process, which owns a 1-device jax).  ``REPRO_DEVICES`` overrides the
device count (default 4; the monoC cases run at 4 and 8).
"""
import os
import sys

N_DEV = int(os.environ.get("REPRO_DEVICES", "4"))
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_DEV}"
)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import compat  # noqa: E402
from repro.compat import shard_map  # noqa: E402
from repro.core import SpGEMMInstance, build_model, partition  # noqa: E402
from repro.distributed import (  # noqa: E402
    build_rowwise_plan,
    fine_spgemm,
    monoC_spgemm,
    rowwise_spgemm,
)
from repro.distributed.plan_ir import (  # noqa: E402
    plan_fine_from_dense,
    plan_monoC_from_dense,
)
from repro.distributed.spgemm_exec import (  # noqa: E402
    unpack_fine_result,
    unpack_monoC_result,
    unpack_rowwise_result,
)
from repro.sparse.structure import random_structure  # noqa: E402


def _random_valued(struct, rng):
    dense = np.zeros(struct.shape, dtype=np.float32)
    r, c = struct.coo()
    dense[r, c] = rng.standard_normal(len(r)).astype(np.float32)
    return dense


def case_rowwise():
    rng = np.random.default_rng(0)
    a_s = random_structure(37, 23, 0.15, rng)
    b_s = random_structure(23, 29, 0.2, rng)
    inst = SpGEMMInstance(a_s, b_s)
    hg = build_model(inst, "rowwise")
    res = partition(hg, 4, eps=0.2, seed=0)
    plan = build_rowwise_plan(inst, res.parts, 4)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    c_local = rowwise_spgemm(a, b, plan, mesh)
    c = unpack_rowwise_result(c_local, plan, 37)
    np.testing.assert_allclose(c, a @ b, rtol=1e-5, atol=1e-5)
    # padded comm never below the combinatorial ideal
    assert plan.comm_words_padded >= plan.comm_words_ideal
    print("OK rowwise ideal=%d padded=%d" % (plan.comm_words_ideal, plan.comm_words_padded))


def _check_on_fine(model: str, seed: int, shape: tuple[int, int, int], moving: str):
    """Plan ``model`` through ``repro.plan`` at p = 4: it runs on the fine
    executor, only the route of the net family it cuts ships, its words
    equal the connectivity, and C == A @ B."""
    import repro

    rng = np.random.default_rng(seed)
    I, K, J = shape
    a_s = random_structure(I, K, 0.18, rng)
    b_s = random_structure(K, J, 0.18, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    handle = repro.plan(a_s, b_s, p=4, model=model, eps=0.2)
    plan = handle.execution_plan
    assert {n for n, r in plan.routes.items() if r.items_ideal} <= {moving}, plan.routes
    report = handle.cost_report()
    assert report["planned_words"] == report["predicted_words"] > 0, report
    got = handle(a[a_s.coo()], b[b_s.coo()])
    np.testing.assert_allclose(got, a @ b, rtol=1e-5, atol=1e-5)
    assert plan.comm_words_padded >= plan.comm_words_ideal
    print("OK %s ideal=%d padded=%d" % (model, plan.comm_words_ideal, plan.comm_words_padded))


def case_outer():
    _check_on_fine("outer", 1, (31, 26, 33), "reduce_c")


def case_columnwise():
    _check_on_fine("columnwise", 2, (19, 22, 17), "expand_a")


def case_rowwise_identity_partition():
    """All rows on one device: zero expand traffic to that device's rows."""
    rng = np.random.default_rng(3)
    a_s = random_structure(16, 12, 0.25, rng)
    b_s = random_structure(12, 14, 0.25, rng)
    inst = SpGEMMInstance(a_s, b_s)
    parts = np.zeros(16, dtype=np.int64)
    plan = build_rowwise_plan(inst, parts, 4, b_part=np.zeros(12, dtype=np.int64))
    assert plan.comm_words_ideal == 0
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    c_local = rowwise_spgemm(a, b, plan, mesh)
    c = unpack_rowwise_result(c_local, plan, 16)
    np.testing.assert_allclose(c, a @ b, rtol=1e-5, atol=1e-5)
    print("OK rowwise_identity")


def _monoC_oracle(seed: int, shape: tuple[int, int, int], block: int, density: float):
    """Build a monoC plan on the block structure, execute on a 2D mesh over
    all devices, check vs dense A @ B, and check the IR's route accounting."""
    p = N_DEV
    rng = np.random.default_rng(seed)
    I, K, J = shape
    a_s = random_structure(I, K, density, rng)
    b_s = random_structure(K, J, density, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    plan, inst = plan_monoC_from_dense(a, b, block, p, seed=seed)
    pr = 2
    pc = p // pr
    mesh = Mesh(np.array(jax.devices()).reshape(pr, pc), ("x", "y"))
    c_local = monoC_spgemm(a, b, plan, mesh, block=block)
    gr, gc = inst.c.shape
    c = unpack_monoC_result(c_local, plan, inst.c, (gr * block, gc * block))[:I, :J]
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)
    assert plan.comm_words_padded >= plan.comm_words_ideal
    for route in plan.routes.values():
        assert route.items_padded >= route.items_ideal
    return plan


def case_monoC():
    plan = _monoC_oracle(0, (36, 28, 32), block=4, density=0.18)
    print(
        "OK monoC p=%d ideal=%d padded=%d"
        % (N_DEV, plan.comm_words_ideal, plan.comm_words_padded)
    )


def case_monoC_blocked():
    plan = _monoC_oracle(1, (48, 40, 32), block=8, density=0.22)
    print(
        "OK monoC_blocked p=%d ideal=%d padded=%d"
        % (N_DEV, plan.comm_words_ideal, plan.comm_words_padded)
    )


def case_monoC_identity_partition():
    """All C blocks (and A/B nonzeros) on device 0: zero expand traffic."""
    rng = np.random.default_rng(2)
    a_s = random_structure(16, 12, 0.3, rng)
    b_s = random_structure(12, 16, 0.3, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    from repro.distributed import build_monoC_plan
    from repro.sparse.bsr import to_bsr

    block = 4
    ab = to_bsr(a, block, block)
    bb = to_bsr(b, block, block)
    inst = SpGEMMInstance(ab.block_structure(), bb.block_structure())
    plan = build_monoC_plan(
        inst,
        np.zeros(inst.c.nnz, dtype=np.int64),
        N_DEV,
        a_part=np.zeros(inst.a.nnz, dtype=np.int64),
        b_part=np.zeros(inst.b.nnz, dtype=np.int64),
        word_size=block * block,
    )
    assert plan.comm_words_ideal == 0
    pr = 2
    mesh = Mesh(np.array(jax.devices()).reshape(pr, N_DEV // pr), ("x", "y"))
    c_local = monoC_spgemm(a, b, plan, mesh, block=block)
    gr, gc = inst.c.shape
    c = unpack_monoC_result(c_local, plan, inst.c, (gr * block, gc * block))[:16, :16]
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)
    print("OK monoC_identity")


def _fine_oracle(seed: int, shape: tuple[int, int, int], density: float, include_nz=False):
    """Build a fine-grained plan, execute expand-expand-reduce on a 1D mesh
    over all devices, check vs dense A @ B, and check that the planned words
    equal the fine hypergraph's connectivity cost (predicted == planned)."""
    p = N_DEV
    rng = np.random.default_rng(seed)
    I, K, J = shape
    a_s = random_structure(I, K, density, rng)
    b_s = random_structure(K, J, density, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    plan, inst = plan_fine_from_dense(a, b, p, seed=seed, include_nz=include_nz)
    from repro.core import evaluate

    hg = build_model(inst, "fine", include_nz=include_nz)
    res = partition(hg, p, eps=0.10, seed=seed)
    # same partitioner invocation as the pipeline: predictions must line up
    predicted = evaluate(hg, res.parts, p).connectivity
    assert plan.comm_words_ideal == predicted, (plan.comm_words_ideal, predicted)
    mesh = Mesh(np.array(jax.devices()), ("x",))
    c_local = fine_spgemm(a, b, plan, mesh)
    c = unpack_fine_result(c_local, plan, inst.c, (I, J))
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)
    assert plan.comm_words_padded >= plan.comm_words_ideal
    for route in plan.routes.values():
        assert route.items_padded >= route.items_ideal
    return plan


def case_fine():
    plan = _fine_oracle(0, (36, 28, 32), density=0.15)
    print(
        "OK fine p=%d ideal=%d padded=%d"
        % (N_DEV, plan.comm_words_ideal, plan.comm_words_padded)
    )


def case_fine_nz():
    plan = _fine_oracle(1, (30, 26, 24), density=0.18, include_nz=True)
    print(
        "OK fine_nz p=%d ideal=%d padded=%d"
        % (N_DEV, plan.comm_words_ideal, plan.comm_words_padded)
    )


def case_fine_identity_partition():
    """All multiplications and nonzeros on device 0: zero traffic on all
    three routes, result still correct."""
    rng = np.random.default_rng(2)
    a_s = random_structure(16, 12, 0.3, rng)
    b_s = random_structure(12, 16, 0.3, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    from repro.distributed import build_fine_plan

    inst = SpGEMMInstance(a_s, b_s)
    zeros = np.zeros(inst.n_mult, dtype=np.int64)
    plan = build_fine_plan(
        inst,
        zeros,
        N_DEV,
        a_part=np.zeros(inst.a.nnz, dtype=np.int64),
        b_part=np.zeros(inst.b.nnz, dtype=np.int64),
        c_part=np.zeros(inst.c.nnz, dtype=np.int64),
    )
    assert plan.comm_words_ideal == 0
    mesh = Mesh(np.array(jax.devices()), ("x",))
    c_local = fine_spgemm(a, b, plan, mesh)
    c = unpack_fine_result(c_local, plan, inst.c, (16, 16))
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)
    print("OK fine_identity")


def case_fine_blocked():
    """Block operands (A 3x3, B 3x6 blocks) on the fine executor family at
    p=N_DEV: C equals the float64 product of the scalar expansions, and the
    route words (items times the block areas 9, 18, 18) equal the blocked
    hypergraph's connectivity."""
    import scipy.sparse as sp

    import repro
    from repro.distributed.plan_ir import measured_route_words

    blocks = ((3, 3), (3, 6))
    rng = np.random.default_rng(17)
    a_s = random_structure(24, 20, 0.2, rng)
    b_s = random_structure(20, 22, 0.2, rng)
    a_vals = rng.standard_normal((a_s.nnz, 3, 3), dtype=np.float32)
    b_vals = rng.standard_normal((b_s.nnz, 3, 6), dtype=np.float32)

    def expand(s, vals, block):
        return sp.bsr_matrix(
            (vals.astype(np.float64), s.csr.indices, s.csr.indptr),
            shape=(s.shape[0] * block[0], s.shape[1] * block[1]),
        ).toarray()

    a64, b64 = expand(a_s, a_vals, blocks[0]), expand(b_s, b_vals, blocks[1])
    want, scale = a64 @ b64, np.abs(a64) @ np.abs(b64)
    for model in ("fine", "monoA", "monoB"):
        handle = repro.plan(a_s, b_s, p=N_DEV, model=model, blocks=blocks)
        plan = handle.execution_plan
        got = handle(a_vals, b_vals)
        err = (np.abs(got - want) / np.maximum(scale, np.finfo(float).tiny)).max()
        assert err <= 1e-4, (model, err)
        report = handle.cost_report()
        items = {n: int((r.recv_key >= 0).sum()) for n, r in plan.routes.items()}
        weighted = 9 * items["expand_a"] + 18 * items["expand_b"] + 18 * items["reduce_c"]
        assert report["predicted_words"] == measured_route_words(plan) == weighted > 0, (
            model, report, items)
        print("OK fine_blocked %s p=%d words=%d" % (model, N_DEV, weighted))


def case_select():
    """End-to-end model selection: sweep every model on a small instance,
    execute every plan, measured == predicted for the replicated-free
    plans (every model but rowwise)."""
    from repro.distributed.registry import MODEL_SPECS
    from repro.distributed.select import sweep_instance

    rng = np.random.default_rng(4)
    a_s = random_structure(32, 24, 0.15, rng)
    b_s = random_structure(24, 28, 0.18, rng)
    inst = SpGEMMInstance(a_s, b_s, name="select_case")
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    recs = sweep_instance(inst, p=N_DEV, a_dense=a, b_dense=b, execute=True)
    by_model = {r["model"]: r for r in recs}
    for model, r in by_model.items():
        if MODEL_SPECS[model].measured == "exact":
            assert r["measured_words"] == r["predicted_words"], (model, r)
        assert r.get("exec_max_err", 1.0) < 1e-4, (model, r)
    best = min(by_model.values(), key=lambda r: r["predicted_words"])
    print("OK select best=%s predicted=%d" % (best["model"], best["predicted_words"]))


def case_runtime():
    """Compile-once runtime: every registry executor AOT-compiled once, value-only
    updates match the dense oracle, zero retraces across >= 10 same-structure
    calls, donation never corrupts caller-held numpy buffers, and the LRU
    returns the identical executable on a same-key lookup."""
    from repro.distributed import runtime
    from repro.distributed.runtime import compile_spgemm
    from repro.distributed.select import build_executable_plan

    p = N_DEV
    rng = np.random.default_rng(7)
    a_s = random_structure(36, 30, 0.15, rng)
    b_s = random_structure(30, 32, 0.18, rng)
    inst = SpGEMMInstance(a_s, b_s, name="runtime_case")
    a1, b1 = _random_valued(a_s, rng), _random_valued(b_s, rng)
    a2, b2 = _random_valued(a_s, rng), _random_valued(b_s, rng)
    ar, ac = a_s.coo()
    br, bc = b_s.coo()

    def vals(a_dense, b_dense, model):
        av, bv = a_dense[ar, ac], b_dense[br, bc]
        if model == "monoC":  # scalar instance == 1x1 blocks
            av, bv = av.reshape(-1, 1, 1), bv.reshape(-1, 1, 1)
        return av, bv

    fine_exe = None
    for model in ("rowwise", "columnwise", "outer", "monoA", "monoB", "monoC", "fine"):
        hg = build_model(inst, model)
        res = partition(hg, p, eps=0.2, seed=0)
        plan = build_executable_plan(inst, model, res.parts, p)
        if model == "monoC":
            mesh = Mesh(np.array(jax.devices()[:p]).reshape(2, p // 2), ("x", "y"))
            exe = compile_spgemm(
                plan, inst.a, inst.b, mesh, block=1, backend="xla", c_structure=inst.c
            )
        else:
            mesh = Mesh(np.array(jax.devices()[:p]), ("x",))
            exe = compile_spgemm(plan, inst.a, inst.b, mesh, c_structure=inst.c)
        # value-only updates: two value sets on the one compiled structure
        for a_d, b_d in ((a1, b1), (a2, b2)):
            got = exe.unpack(exe(*vals(a_d, b_d, model)))[:36, :32]
            np.testing.assert_allclose(got, a_d @ b_d, rtol=1e-4, atol=1e-4)
        # cache hit returns the identical executable object
        assert (
            compile_spgemm(
                plan, inst.a, inst.b, mesh,
                **(dict(block=1, backend="xla") if model == "monoC" else {}),
            )
            is exe
        ), model
        if model == "fine":
            fine_exe = exe

    # zero retraces across >= 10 same-structure calls
    av, bv = vals(a1, b1, "fine")
    n0 = runtime.trace_count()
    for _ in range(10):
        out = fine_exe(av, bv)
    jax.block_until_ready(out)
    assert runtime.trace_count() == n0, (runtime.trace_count(), n0)

    # donation doesn't corrupt reuse: numpy inputs survive repeated calls
    av_copy, bv_copy = av.copy(), bv.copy()
    r1 = np.asarray(fine_exe(av, bv))
    r2 = np.asarray(fine_exe(av, bv))
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(av, av_copy)
    np.testing.assert_array_equal(bv, bv_copy)

    # mismatched-structure values raise
    try:
        fine_exe(av[:-1], bv)
    except ValueError:
        pass
    else:
        raise AssertionError("short A values did not raise")

    info = runtime.cache_info()
    assert info["hits"] >= 4, info
    print("OK runtime p=%d traces=%d" % (p, runtime.trace_count()))


def case_api():
    """The repro.api front door: one call from structures to dense C for
    every executable model — no caller-visible mesh/dtype/model
    special-casing — plus model="auto" selection and the cost report's
    predicted == planned identity for the replicated-free models."""
    import repro

    p = N_DEV
    rng = np.random.default_rng(11)
    a_s = random_structure(34, 27, 0.15, rng)
    b_s = random_structure(27, 31, 0.18, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    want = a @ b
    a_vals = a[a_s.coo()]
    b_vals = b[b_s.coo()]
    for model in repro.executable_models():
        handle = repro.plan(a_s, b_s, p=p, model=model)
        got = handle(a_vals, b_vals)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4, err_msg=model)
        report = handle.cost_report()
        if handle.spec.measured == "exact":
            assert report["planned_words"] == report["predicted_words"], report
    auto = repro.plan(a_s, b_s, p=p, model="auto")
    assert auto.model in repro.executable_models()
    assert sum(r["selected"] for r in auto.selection) == 1
    assert min(r["predicted_words"] for r in auto.selection) == (
        auto.cost_report()["predicted_words"]
    )
    np.testing.assert_allclose(auto(a_vals, b_vals), want, rtol=1e-4, atol=1e-4)
    print("OK api p=%d auto=%s" % (p, auto.model))


def case_summa():
    """Sparse SUMMA baseline at p=N_DEV: the oblivious executor matches the
    dense oracle through the front door, its route tables ship exactly the
    closed-form nnz(A)(pc-1) + nnz(B)(pr-1) words, and the SAME plan executes
    correctly when the caller forces non-square (pr, pc) factorizations —
    the flattened all_to_all is independent of the physical mesh shape."""
    import repro
    from repro.distributed.plan_ir import measured_route_words
    from repro.distributed.summa import build_summa_plan, summa_words_ideal

    p = N_DEV
    rng = np.random.default_rng(13)
    a_s = random_structure(33, 26, 0.18, rng)
    b_s = random_structure(26, 29, 0.2, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    want = a @ b
    handle = repro.plan(a_s, b_s, p=p, model="summa2d")
    plan = handle.execution_plan
    assert measured_route_words(plan) == plan.stats["words_analytic"]
    assert plan.stats["words_analytic"] == summa_words_ideal(
        handle.instance, plan.pr, plan.pc
    )
    got = handle(a[a_s.coo()], b[b_s.coo()])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    # every factorization of p, including the degenerate 1D ones
    inst = handle.instance
    for pr in range(1, p + 1):
        if p % pr:
            continue
        pc = p // pr
        forced = build_summa_plan(inst, p, pr=pr, pc=pc)
        assert forced.stats["words_analytic"] == summa_words_ideal(inst, pr, pc)
        h2 = repro.PlannedSpGEMM(
            instance=inst, model="summa2d", hypergraph=None, partition=None,
            execution_plan=forced,
        )
        got2 = h2(a[a_s.coo()], b[b_s.coo()])
        np.testing.assert_allclose(
            got2, want, rtol=1e-4, atol=1e-4, err_msg=f"pr={pr} pc={pc}"
        )
    print(
        "OK summa p=%d mesh=(%d,%d) words=%d"
        % (p, plan.pr, plan.pc, plan.stats["words_analytic"])
    )


def case_api_odd_p():
    """monoC through the front door at an ODD p: the registry's (1, p) mesh
    fallback replaces the old caller-side 'odd p skipped' quirk."""
    import repro

    p = 3
    assert N_DEV >= p
    rng = np.random.default_rng(12)
    a_s = random_structure(20, 16, 0.2, rng)
    b_s = random_structure(16, 18, 0.2, rng)
    a = _random_valued(a_s, rng)
    b = _random_valued(b_s, rng)
    handle = repro.plan(a_s, b_s, p=p, model="monoC")
    devices = jax.devices()[:p]
    got = handle.compile(devices=devices)(a[a_s.coo()], b[b_s.coo()])
    np.testing.assert_allclose(got, a @ b, rtol=1e-4, atol=1e-4)
    print("OK api_odd_p p=%d" % p)


def case_compressed_psum():
    """EF-int8 compressed all-reduce: approximates the exact mean within the
    quantization scale, and error feedback drives the running average of the
    compressed stream toward the exact mean."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.training.compression import compressed_psum_mean

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((4, 64, 32)).astype(np.float32)
    exact = xs.mean(axis=0)

    def body(x, err):
        return compressed_psum_mean(x[0], err[0], "x")

    fn = jax.jit(
        shard_map(
            lambda x, e: tuple(o[None] for o in body(x, e)),
            mesh=mesh,
            in_specs=(P("x"), P("x")),
            out_specs=(P("x"), P("x")),
        )
    )
    err = np.zeros_like(xs)
    means = []
    for _ in range(8):
        mean, err = fn(jnp.asarray(xs), jnp.asarray(err))
        means.append(np.asarray(mean[0]))
        err = np.asarray(err)
    # single-shot error bounded by the max quantization scale
    scale = np.abs(xs).max() / 127.0
    assert np.abs(means[0] - exact).max() <= 4 * scale
    # error feedback: the running average converges below one-shot error
    avg = np.mean(means, axis=0)
    assert np.abs(avg - exact).max() < np.abs(means[0] - exact).max() + 1e-7
    # wire format really is int8-sized: compression ratio 2x vs bf16
    from repro.training.compression import compression_ratio
    assert compression_ratio() == 2.0
    print("OK compressed_psum")


def case_moe_ep():
    """Expert-parallel shard_map MoE must match the single-device fallback
    numerically (same routing, same capacity semantics)."""
    import dataclasses
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import get_smoke_config
    from repro.models import init_params, train_loss

    cfg = get_smoke_config("dbrx-132b")
    # capacity factor high enough that no token is ever dropped: the two
    # dispatch paths then compute identical math (drop ORDER differs between
    # global-capacity fallback and per-shard-capacity EP, by design)
    cfg = dataclasses.replace(
        cfg,
        dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
    )
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    # fallback: no mesh context
    loss_ref, _ = jax.jit(lambda p, b: train_loss(p, cfg, b))(params, batch)

    # EP path: mesh with model axis 2 (4 experts / 2 columns), data axis 2
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    jax.set_mesh(mesh)
    try:
        from repro.models.sharding import param_shardings, batch_sharding
        psh = param_shardings(cfg, mesh)
        bsh = {k: batch_sharding(mesh, v.shape[0], v.ndim) for k, v in batch.items()}
        loss_ep, _ = jax.jit(
            lambda p, b: train_loss(p, cfg, b),
            in_shardings=(psh, bsh),
        )(jax.device_put(params, psh), {k: jax.device_put(v, bsh[k]) for k, v in batch.items()})
    finally:
        pass
    assert abs(float(loss_ref) - float(loss_ep)) < 2e-4, (loss_ref, loss_ep)
    print("OK moe_ep", float(loss_ref), float(loss_ep))


def case_session():
    """Resilient session at p=N_DEV: an MCL-style drift loop with faults
    scripted at four stage boundaries (every product checked against numpy),
    then kill-and-restore — a fresh session rebuilds its pool from the plan
    store with zero retraces."""
    import shutil
    import tempfile

    import repro
    from repro.distributed import runtime
    from repro.resilience import FaultPolicy
    from repro.testing import faults

    p = N_DEV
    policy = FaultPolicy(backoff_s=0.0)
    store = tempfile.mkdtemp(prefix="repro_session_store_")
    try:
        rng = np.random.default_rng(5)
        n = 48
        M = (rng.random((n, n)) * (rng.random((n, n)) < 0.2)).astype(np.float32)
        M[np.arange(n), np.arange(n)] = 1.0
        s = repro.session(p=p, model="rowwise", policy=policy, store_dir=store)
        hist = []
        schedule = {"partition": [1], "compile": [1], "execute": [2], "store_save": [0]}
        with faults.scripted(schedule) as scripts:
            for _ in range(4):
                C = np.asarray(s.multiply(M, M))
                np.testing.assert_allclose(C, M @ M, rtol=2e-4, atol=2e-4)
                hist.append(M)
                # prune + renormalize: the structure drifts for the next round
                C[C < np.quantile(C[C > 0], 0.3)] = 0.0
                col = C.sum(axis=0)
                M = (C / np.where(col > 0, col, 1.0)).astype(np.float32)
                M[np.arange(n), np.arange(n)] += 0.5
        for stage, script in scripts.items():
            assert script.fired == len(schedule[stage]), (stage, script.seen)
        kinds = [e.kind for e in s.events]
        assert kinds.count("cold_replan") + kinds.count("warm_replan") == 4, kinds
        assert kinds.count("warm_replan") >= 1, kinds

        # the crash: a fresh session restores every entry from the store
        del s
        s2 = repro.session(p=p, model="rowwise", policy=policy, store_dir=store)
        before = runtime.trace_count()
        for M_old in hist:
            C = np.asarray(s2.multiply(M_old, M_old))
            np.testing.assert_allclose(C, M_old @ M_old, rtol=2e-4, atol=2e-4)
        assert runtime.trace_count() == before, "restored plans must not retrace"
        kinds2 = [e.kind for e in s2.events]
        assert kinds2.count("restored") == len(hist), kinds2
        assert "cold_replan" not in kinds2 and "warm_replan" not in kinds2
        print(
            "OK session p=%d warm=%d restored=%d"
            % (p, kinds.count("warm_replan"), len(hist))
        )
    finally:
        shutil.rmtree(store, ignore_errors=True)


def case_serve():
    """Serving tier at p=N_DEV: batched executors for all four executable
    models match the per-call path and the dense oracle; ragged batch sizes
    inside one capacity bucket share a single AOT executable with zero
    retraces; repeated batched calls reusing the same numpy value buffers are
    donation-safe; and the serving loop drains a mixed window batched."""
    import repro
    from repro.distributed import runtime
    from repro.distributed.runtime import batch_bucket
    from repro.launch.serve import SpGEMMServer

    p = N_DEV
    rng = np.random.default_rng(9)
    a_s = random_structure(34, 28, 0.15, rng)
    b_s = random_structure(28, 30, 0.18, rng)
    a_stack = lambda m: rng.standard_normal((m, a_s.nnz)).astype(np.float32)  # noqa: E731
    b_stack = lambda m: rng.standard_normal((m, b_s.nnz)).astype(np.float32)  # noqa: E731

    def dense(s, vals):
        d = np.zeros(s.shape, np.float32)
        d[s.coo()] = vals
        return d

    for model in repro.executable_models():
        planned = repro.plan(a_s, b_s, p=p, model=model)
        exe_one = planned.compile()
        exe_batch = planned.compile(batch=4)
        av, bv = a_stack(4), b_stack(4)
        got = exe_batch(av, bv)
        assert got.shape == (4, 34, 30), (model, got.shape)
        for i in range(4):
            want = dense(a_s, av[i]) @ dense(b_s, bv[i])
            np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4, err_msg=model)
            np.testing.assert_allclose(
                exe_one(av[i], bv[i]), want, rtol=1e-4, atol=1e-4, err_msg=model
            )

    # ragged batches in one bucket: m in {3, 4} -> capacity-4 executable,
    # zero retraces after the first batched call compiled the bucket
    planned = repro.plan(a_s, b_s, p=p, model="fine")
    exe4 = planned.compile(batch=3)
    assert exe4.batch_capacity == batch_bucket(3) == 4
    exe4(a_stack(2), b_stack(2))  # bucket warm
    n0 = runtime.trace_count()
    for m in (1, 2, 3, 4):
        got = exe4(a_stack(m), b_stack(m))
        assert got.shape[0] == m, (m, got.shape)
    assert runtime.trace_count() == n0, "ragged batches inside one bucket retraced"
    # the handle wrapper is fresh per compile(); the AOT executable is shared
    assert planned.compile(batch=4).runtime is exe4.runtime, (
        "same bucket must hit the runtime LRU"
    )

    # donation safety: the same numpy buffers survive repeated batched calls
    av, bv = a_stack(4), b_stack(4)
    av_copy, bv_copy = av.copy(), bv.copy()
    r1 = np.asarray(exe4(av, bv))
    r2 = np.asarray(exe4(av, bv))
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(av, av_copy)
    np.testing.assert_array_equal(bv, bv_copy)

    # the loop end-to-end at this p: one window of same-structure traffic
    # rides batched dispatches and every result matches the oracle
    server = SpGEMMServer(p=p, model="fine", max_batch=4, batch_window=8)
    reqs = [
        server.submit((a_s, a_stack(1)[0]), (b_s, b_stack(1)[0])) for _ in range(6)
    ]
    server.drain()
    assert server.stats.completed == 6, server.stats
    assert server.stats.dispatches == 2, server.stats  # 6 reqs / max_batch 4
    for r in reqs:
        want = dense(a_s, r.a_vals) @ dense(b_s, r.b_vals)
        np.testing.assert_allclose(r.result, want, rtol=1e-4, atol=1e-4)
    print("OK serve p=%d traces=%d" % (p, runtime.trace_count()))


if __name__ == "__main__":
    assert len(jax.devices()) == N_DEV, jax.devices()
    for name in sys.argv[1:] or [
        "rowwise",
        "outer",
        "columnwise",
        "rowwise_identity_partition",
    ]:
        globals()[f"case_{name}"]()
    print("ALL OK")
