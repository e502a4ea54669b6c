"""End-to-end behaviour tests: the hypergraph MODEL's predicted
communication equals the EXECUTOR plan's scheduled communication (Lemma 4.2
made executable), across random instances and partitions."""
import numpy as np
import pytest

from repro.core import SpGEMMInstance, build_model, evaluate, partition
from repro.distributed import build_rowwise_plan, measured_route_words
from repro.distributed.registry import get_spec
from repro.sparse.structure import random_structure


def _inst(seed, shape=(40, 28, 33), density=0.15):
    rng = np.random.default_rng(seed)
    a = random_structure(shape[0], shape[1], density, rng)
    b = random_structure(shape[1], shape[2], density, rng)
    return SpGEMMInstance(a, b)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("p", [2, 4])
def test_rowwise_model_matches_executor_plan(seed, p):
    """The row-wise hypergraph (with B nonzero-vertices pinned to their
    owners) predicts, via the connectivity metric with unit net costs,
    exactly the number of B-row transfers the executor schedules."""
    inst = _inst(seed)
    I, K, J = inst.shape
    hg = build_model(inst, "rowwise", include_nz=True)
    res = partition(build_model(inst, "rowwise"), p, eps=0.3, seed=seed)
    row_part = res.parts[:I]
    b_part = np.arange(K) % p  # executor default distribution

    plan = build_rowwise_plan(inst, row_part, p, b_part=b_part)

    # hypergraph prediction: vertices = rows + B-row vertices
    parts = np.concatenate([row_part, b_part])
    hg.net_cost = np.ones(hg.n_nets, dtype=np.int64)  # count B-row transfers
    costs = evaluate(hg, parts, p)
    assert costs.connectivity == plan.comm_words_ideal


@pytest.mark.parametrize("seed", [3, 4])
def test_outer_model_matches_fold_plan(seed):
    """Outer-product fold volume: (distinct contributing k-parts - 1) summed
    over C nonzeros — model and plan must agree."""
    inst = _inst(seed)
    p = 4
    hg = build_model(inst, "outer")
    res = partition(hg, p, eps=0.3, seed=seed)
    plan = get_spec("outer").lower(inst, res.parts, p)
    costs = evaluate(hg, res.parts, p)
    # outer model nets are C nonzeros with unit cost; connectivity = the
    # C partials the reduce route ships
    assert costs.connectivity == measured_route_words(plan) == plan.comm_words_ideal


def test_partition_quality_transfers_to_executor(tmp_path):
    """A better partition (lower hypergraph cut) yields a plan with less
    scheduled traffic than a random partition — the paper's premise."""
    inst = _inst(7, shape=(60, 40, 50), density=0.12)
    I, K, J = inst.shape
    p = 4
    hg = build_model(inst, "rowwise")
    good = partition(hg, p, eps=0.3, seed=0).parts
    rng = np.random.default_rng(0)
    bad = rng.integers(0, p, size=I)
    b_part = np.arange(K) % p
    plan_good = build_rowwise_plan(inst, good, p, b_part=b_part)
    plan_bad = build_rowwise_plan(inst, bad, p, b_part=b_part)
    assert plan_good.comm_words_ideal < plan_bad.comm_words_ideal
