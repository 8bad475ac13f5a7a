"""Property-based tests (hypothesis; the conftest stub degrades to fixed
deterministic examples when the real package is absent) for the pipeline's
combinatorial invariants:

* sorting (core/sorting.py): every sort method returns a PERMUTATION of the
  input indices — no index dropped, none duplicated — for arbitrary sizes
  and feature clouds; `chain_length` is invariant under which permutation
  representation is fed in.
* chain planning (core/pipeline.py): `plan_chains` covers every position of
  the sorted order exactly once, contiguously, with balanced lengths, for
  arbitrary (n, workers).
* lockstep packing: the `_row_index` rows round-trip back to the exact
  chains (no label corruption through padding), and padding is only ever a
  SUFFIX of a chain's row sequence — a -1 never reappears before a live
  index, which is the alignment property the zero-RHS padding no-op relies
  on.
"""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import pipeline
from repro.core.sorting import chain_length, sort_features

METHODS = ("greedy", "grouped", "hilbert", "random", "none")


def _feats(n: int, seed: int, f: int = 6) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, f))


# ----------------------------------------------------------------- sorting

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 60), st.integers(0, 2**31 - 1))
def test_sort_methods_return_permutations(n, seed):
    feats = _feats(n, seed)
    for method in METHODS:
        order = sort_features(feats, method)
        assert sorted(np.asarray(order).tolist()) == list(range(n)), method


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 40), st.integers(0, 2**31 - 1))
def test_chain_length_nonnegative_and_zero_for_identical(n, seed):
    feats = _feats(n, seed)
    order = sort_features(feats, "greedy")
    assert chain_length(feats, order) >= 0.0
    same = np.ones((n, 3))
    assert chain_length(same, np.arange(n)) == 0.0


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 50), st.integers(0, 2**31 - 1))
def test_greedy_no_worse_than_identity_chain(n, seed):
    """Greedy (Algorithm 1 from index 0) takes, at every step, the nearest
    point not yet on its chain, so no step is longer than the step to the
    next unvisited point in identity order. (Its TOTAL path can exceed the
    identity order's: a nearest-neighbour chain may end with long jumps.)"""
    feats = _feats(n, seed)
    order = sort_features(feats, "greedy")
    assert order[0] == 0
    dist = np.linalg.norm(feats[:, None] - feats[None], axis=-1)
    seen = np.zeros(n, dtype=bool)
    for a, b in zip(order[:-1], order[1:]):
        seen[a] = True
        ident_next = np.flatnonzero(~seen)[0]
        assert dist[a, b] <= dist[a, ~seen].min() + 1e-9
        assert dist[a, b] <= dist[a, ident_next] + 1e-9


# ---------------------------------------------------------- chain planning

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 80), st.integers(1, 12))
def test_plan_chains_partitions_exactly_once(n, workers):
    order = np.random.default_rng(n * 131 + workers).permutation(n)
    subs = pipeline.plan_chains(order, workers)
    assert len(subs) == workers
    flat = np.concatenate([s for s in subs]) if subs else np.zeros(0)
    np.testing.assert_array_equal(flat, order)       # contiguous cover
    counts = np.bincount(flat.astype(int), minlength=n)
    assert (counts == 1).all()                       # each index exactly once
    lens = [len(s) for s in subs]
    assert max(lens) - min(lens) <= 1                # balanced


# --------------------------------------------------------- lockstep packing

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 60), st.integers(1, 9), st.integers(0, 2**31 - 1))
def test_lockstep_rows_round_trip_chains(n, workers, seed):
    """Packing chains into lockstep rows and unpacking them recovers every
    chain bit-for-bit: padded (-1) slots appear only after a chain is
    exhausted, and no label ever migrates between chains."""
    order = np.random.default_rng(seed).permutation(n)
    subs = pipeline.plan_chains(order, workers)
    length = max(len(s) for s in subs)
    rows = [pipeline._row_index(subs, t) for t in range(length)]

    for w, sub in enumerate(subs):
        col = [int(rows[t][w]) for t in range(length)]
        live = [v for v in col if v >= 0]
        np.testing.assert_array_equal(live, sub)     # no label corruption
        # padding is a strict suffix: once -1, always -1
        seen_pad = False
        for v in col:
            if v < 0:
                seen_pad = True
            else:
                assert not seen_pad, "live index after padding"

    # each row's live entries are disjoint across chains (one system is
    # solved by exactly one chain)
    all_live = [v for row in rows for v in row if v >= 0]
    assert sorted(all_live) == sorted(order.tolist())


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 40), st.integers(1, 8))
def test_phase_mask_monotone_shutdown(n, workers):
    """PhaseMask only ever turns chains OFF; padded_rows is always the
    complement of active and ends all-padded once every chain finished."""
    live = np.random.default_rng(n + workers).random(workers) < 0.8
    mask = pipeline.PhaseMask(live)
    np.testing.assert_array_equal(mask.padded_rows, ~mask.active)
    np.testing.assert_array_equal(mask.active, live)
    for w in range(workers):
        before = mask.active.sum()
        mask.finish(w)
        assert mask.active.sum() <= before
        assert not mask.active[w]
        np.testing.assert_array_equal(mask.padded_rows, ~mask.active)
    assert not mask.any_active


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 10), st.integers(0, 2**31 - 1))
def test_phase_mask_finished_excludes_never_live(workers, seed):
    """`finished` counts genuine active→inactive retirements only:
    never-live sharding fill slots must not inflate it (the old
    `(~active).sum()` counted them), and double-finishing is idempotent."""
    live = np.random.default_rng(seed).random(workers) < 0.6
    mask = pipeline.PhaseMask(live)
    assert mask.finished == 0
    for w in range(workers):
        mask.finish(w)
        mask.finish(w)   # idempotent: a slot retires its chain once
    assert mask.finished == int(live.sum())


def test_phase_mask_refill_slot_table():
    """Streaming slot table: refill reopens a retired slot under a new
    chain id, finished counts once per retired chain across refills, and
    refilling a LIVE slot is rejected."""
    import pytest

    mask = pipeline.PhaseMask(np.zeros(3, dtype=bool))
    assert not mask.any_active and (mask.chain == -1).all()
    mask.refill(1, 7)
    assert mask.active[1] and mask.chain[1] == 7
    np.testing.assert_array_equal(mask.padded_rows, [True, False, True])
    with pytest.raises(ValueError):
        mask.refill(1, 8)
    mask.finish(1)
    assert mask.finished == 1 and not mask.any_active
    mask.refill(1, 8)
    mask.refill(0, 9)
    assert mask.chain[1] == 8 and mask.chain[0] == 9
    mask.finish(1)
    mask.finish(0)
    assert mask.finished == 3      # one per retired chain, not per slot
    mask.finish(2)                 # never-live slot: no-op for the count
    assert mask.finished == 3


# ------------------------------------------------- GRF sampling contract
# (pde/grf.py: fold_in key derivation — the label-expansion waves rebuild
#  any single draw from its index, so these properties are load-bearing)

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 6), st.integers(1, 6))
def test_grf_batch_prefix_stable(seed, m, extra):
    """The first m draws of a size-(m+extra) batch equal a size-m batch."""
    import jax
    from repro.pde.grf import GRFSpec, sample_grf_batch

    spec = GRFSpec(nx=8, ny=8)
    key = jax.random.PRNGKey(seed)
    small, _ = sample_grf_batch(spec, key, m)
    big, _ = sample_grf_batch(spec, key, m + extra)
    np.testing.assert_array_equal(np.asarray(small), np.asarray(big)[:m])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 7))
def test_grf_batch_draw_equals_single_fold_in(seed, i):
    """Draw i of a batch ≡ sample_grf(spec, fold_in(key, i)) bitwise —
    vmap vs single-call equivalence AND the fold_in indexing contract."""
    import jax
    from repro.pde.grf import GRFSpec, sample_grf, sample_grf_batch

    spec = GRFSpec(nx=8, ny=8)
    key = jax.random.PRNGKey(seed)
    fields, feats = sample_grf_batch(spec, key, 8)
    f1, l1 = sample_grf(spec, jax.random.fold_in(key, i))
    np.testing.assert_array_equal(np.asarray(fields)[i], np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(feats)[i], np.asarray(l1))


def test_grf_batch_keys_subset_indexing():
    """batch_keys accepts an index array: keys for an arbitrary subset of
    draws match the corresponding rows of the full key batch."""
    import jax
    from repro.pde.grf import batch_keys

    key = jax.random.PRNGKey(5)
    full = np.asarray(batch_keys(key, 10))
    sub = np.asarray(batch_keys(key, np.array([7, 2, 2, 9])))
    np.testing.assert_array_equal(sub, full[[7, 2, 2, 9]])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_grf_dtype_axis(seed):
    """The dtype axis: fp32 draws come back fp32 end to end (field AND
    latent), finite, zero-mean, and with the spectrum actually applied
    (non-trivial spatial correlation). fp64 stays the default."""
    import jax
    import jax.numpy as jnp
    from repro.pde.grf import GRFSpec, sample_grf

    spec = GRFSpec(nx=16, ny=16)
    key = jax.random.PRNGKey(seed)
    f64, l64 = sample_grf(spec, key)
    f32, l32 = sample_grf(spec, key, jnp.float32)
    assert f64.dtype == jnp.float64 and l64.dtype == jnp.float64
    assert f32.dtype == jnp.float32 and l32.dtype == jnp.float32
    f = np.asarray(f32, np.float64)
    assert np.isfinite(f).all()
    np.testing.assert_allclose(f.mean(), 0.0, atol=1e-6)
    # smoothness: neighbor differences much smaller than the field scale
    assert np.abs(np.diff(f, axis=0)).max() < np.abs(f).max()
