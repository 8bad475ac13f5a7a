"""Sorting Algorithm 1 + scalable variants: permutation validity, greedy
local optimality, chain-length reduction — incl. hypothesis property tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.sorting import (chain_length, greedy_sort,
                                grouped_greedy_sort, hilbert_index,
                                hilbert_sort, pairwise_sq_dists,
                                sort_features)


def _feats(n=50, f=6, seed=0):
    return np.random.default_rng(seed).standard_normal((n, f))


@pytest.mark.parametrize("method", ["greedy", "grouped", "hilbert",
                                    "random", "none"])
def test_sort_is_permutation(method):
    feats = _feats(64)
    order = sort_features(feats, method)
    assert sorted(order.tolist()) == list(range(64))


def test_greedy_is_locally_nearest():
    """Each next element is the nearest unused one (Algorithm 1 line 5-8)."""
    feats = _feats(40)
    order = greedy_sort(feats)
    d = np.sqrt(pairwise_sq_dists(feats))
    used = {order[0]}
    for a, b in zip(order[:-1], order[1:]):
        cand = [j for j in range(len(feats)) if j not in used]
        nearest = min(cand, key=lambda j: d[a, j])
        assert np.isclose(d[a, b], d[a, nearest])
        used.add(b)


@pytest.mark.parametrize("method", ["greedy", "grouped", "hilbert"])
def test_sort_shortens_chain(method):
    feats = _feats(128, f=4)
    base = chain_length(feats, np.arange(len(feats)))
    sortd = chain_length(feats, sort_features(feats, method))
    assert sortd < base * 0.9, (method, sortd, base)


def test_greedy_beats_random_ordering():
    feats = _feats(100)
    rand = chain_length(feats, sort_features(feats, "random"))
    greedy = chain_length(feats, sort_features(feats, "greedy"))
    assert greedy < rand


@given(st.integers(1, 60), st.integers(1, 8), st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_property_sort_permutation_and_improvement(n, f, seed):
    """What Algorithm 1 guarantees: a permutation that starts at `start`,
    each step to a nearest unvisited point. (The chain need not be shorter
    than the identity order: n=4 f=5 seed=2826 is a counterexample.)"""
    feats = np.random.default_rng(seed).standard_normal((n, f))
    start = seed % n
    order = greedy_sort(feats, start=start)
    assert sorted(order.tolist()) == list(range(n))
    assert order[0] == start
    # squared distances by differences; the sort's expanded form rounds at
    # ~1e-16 of the squared norms, far inside the 1e-9 relative slack
    slack = 1e-9 * (1.0 + np.max(np.sum(feats**2, axis=1)))
    for i in range(n - 1):
        d2 = np.sum((feats[order[i + 1:]] - feats[order[i]])**2, axis=1)
        assert d2[0] <= d2.min() + slack, (i, d2[0], d2.min())


@given(st.integers(2, 8))
@settings(max_examples=8, deadline=None)
def test_hilbert_index_is_bijective_on_grid(bits):
    side = 1 << bits
    x, y = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    d = hilbert_index(x.ravel(), y.ravel(), bits)
    assert sorted(d.tolist()) == list(range(side * side))


def test_hilbert_index_locality():
    """Consecutive Hilbert indices are grid neighbours (curve continuity)."""
    bits = 4
    side = 1 << bits
    x, y = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    d = hilbert_index(x.ravel(), y.ravel(), bits)
    order = np.argsort(d)
    xs, ys = x.ravel()[order], y.ravel()[order]
    step = np.abs(np.diff(xs)) + np.abs(np.diff(ys))
    assert (step == 1).all()


def test_grouped_matches_greedy_for_small_n():
    feats = _feats(30)
    np.testing.assert_array_equal(grouped_greedy_sort(feats, group_size=1000),
                                  greedy_sort(feats))


def test_sorting_scales_to_large_n():
    """hilbert path handles 20k points in a second-ish (App. E.2.2 posture)."""
    feats = _feats(20_000, f=16)
    order = hilbert_sort(feats)
    assert sorted(order.tolist()) == list(range(20_000))


def _grf_feature_cloud(n=96, seed=4):
    """Sorting features of an actual GRF-sampled family (what the datagen
    pipeline hands to `sort_features`), not synthetic gaussians."""
    import jax

    from repro.pde.registry import get_family

    fam = get_family("darcy", nx=12, ny=12)
    batch = fam.sample_batch(jax.random.PRNGKey(seed), n)
    return np.asarray(batch.features)


def test_hilbert_beats_unsorted_on_grf_cloud():
    """The scalable App. E.2.2 variant must still shorten the recycle chain
    on a realistic GRF feature cloud (small greedy buckets force the
    Hilbert-index stage itself to do the work)."""
    feats = _grf_feature_cloud()
    base = chain_length(feats, np.arange(len(feats)))
    sortd = chain_length(feats, hilbert_sort(feats, greedy_bucket=16))
    assert sortd < base, (sortd, base)


def test_grouped_greedy_beats_unsorted_on_grf_cloud():
    """grouped_greedy with groups far smaller than N (the parallel-sort
    regime) must still beat the unsorted order on chain length."""
    feats = _grf_feature_cloud()
    base = chain_length(feats, np.arange(len(feats)))
    sortd = chain_length(feats, grouped_greedy_sort(feats, group_size=24))
    assert sortd < base, (sortd, base)


def test_sort_features_rejects_unknown_method():
    with pytest.raises(KeyError, match="unknown sort method"):
        sort_features(_feats(8), "simulated-annealing")
