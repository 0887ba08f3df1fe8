"""Tests for Sensitivity-based Rank Allocation (paper §IV)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sra import sra_allocate, uniform_allocation

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


@given(st.integers(2, 8), st.integers(10, 60), st.integers(0, 100))
def test_budget_conserved(layers, budget, seed):
    rng = np.random.default_rng(seed)
    opt = rng.integers(1, 32, size=layers)

    def ev(r):
        return -float(sum((a - b) ** 2 for a, b in zip(r, opt)))

    max_ranks = [64] * layers
    budget = min(budget, sum(max_ranks))
    res = sra_allocate(ev, layers, budget, max_ranks, max_iters=10)
    assert sum(res.ranks) == budget
    assert all(1 <= r <= 64 for r in res.ranks)
    for alloc, _ in res.history:
        assert sum(alloc) == budget


def test_beats_uniform_on_heterogeneous():
    """Layers with very different sensitivity -> SRA must beat uniform."""
    weights = np.array([10.0, 1.0, 0.1, 5.0])
    opt = np.array([40, 8, 2, 30])

    def ev(r):
        return -float(np.sum(weights * (np.array(r) - opt) ** 2))

    budget = int(opt.sum())
    uni = uniform_allocation(4, budget, [64] * 4)
    res = sra_allocate(ev, 4, budget, [64] * 4, delta0=8, max_iters=60)
    assert res.accuracy > ev(uni)


def test_respects_max_ranks():
    def ev(r):
        return float(sum(r))  # monotone: wants all rank everywhere

    res = sra_allocate(ev, 3, 20, [8, 8, 8], max_iters=10)
    assert sum(res.ranks) == 20
    assert all(r <= 8 for r in res.ranks)


def test_budget_exceeds_capacity_raises():
    with pytest.raises(ValueError):
        sra_allocate(lambda r: 0.0, 2, 100, [8, 8])


def test_delta_decay_converges():
    opt = [30, 10]

    def ev(r):
        return -float((r[0] - opt[0]) ** 2 + (r[1] - opt[1]) ** 2)

    res = sra_allocate(ev, 2, 40, [64, 64], delta0=16, alpha=0.3,
                       max_iters=50)
    assert abs(res.ranks[0] - 30) <= 2 and abs(res.ranks[1] - 10) <= 2


def test_memoization_bounds_evals():
    calls = []

    def ev(r):
        calls.append(tuple(r))
        return 0.0

    res = sra_allocate(ev, 4, 16, [16] * 4, max_iters=8)
    assert res.evals == len(set(calls))
