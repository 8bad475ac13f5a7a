"""End-to-end SKR datagen: dataset validity, fault-injection + warm resume
(recycle space survives), chunk-parallel decomposition (App. E.2.2)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.skr import (SKRConfig, SKRGenerator, generate_dataset,
                            generate_dataset_baseline,
                            generate_dataset_chunked)
from repro.pde.registry import get_family, list_families
from repro.solvers.types import KrylovConfig

KC = KrylovConfig(m=30, k=10, tol=1e-8, maxiter=6000)
CFG = SKRConfig(krylov=KC, precond="jacobi")


@pytest.mark.parametrize("inner_dtype", ["float64", "float32"])
@pytest.mark.parametrize("engine", ["sequential", "batched"])
@pytest.mark.parametrize("family", list_families())
def test_datagen_produces_valid_dataset(family, engine, inner_dtype):
    """Every family through the chunked pipeline, on both engines and both
    inner precisions: every label solves its system to tol in fp64."""
    fam = get_family(family, nx=16, ny=16)
    cfg = dataclasses.replace(
        CFG, krylov=dataclasses.replace(KC, inner_dtype=inner_dtype))
    chunks = generate_dataset_chunked(fam, jax.random.PRNGKey(0), 6, cfg,
                                      workers=2, engine=engine)
    assert len(chunks) == 2
    order = np.concatenate([ch.order for ch in chunks])
    assert sorted(order.tolist()) == list(range(6))
    # every solution actually solves its system
    batch = fam.sample_batch(jax.random.PRNGKey(0), 6)
    from repro.core.skr import _problem_op_of

    for ch in chunks:
        n = len(ch.order)
        assert ch.inputs.shape == (n, 16, 16)
        assert ch.solutions.shape == (n, 16, 16)
        assert np.isfinite(ch.solutions).all()
        assert all(s.converged for s in ch.stats.per_system)
        for pos, i in enumerate(ch.order.tolist()):
            a = _problem_op_of(batch, i).to_dense()
            b = np.asarray(batch.b[i], dtype=np.float64).reshape(-1)
            r = np.linalg.norm(b - a @ ch.solutions[pos].reshape(-1))
            assert r <= KC.tol * np.linalg.norm(b) * 1.1, (i, r)


@pytest.mark.parametrize("sort_method",
                         ["greedy", "grouped", "hilbert", "random", "none"])
def test_solutions_independent_of_solve_order(sort_method):
    """SKR (sorted) and GMRES (unsorted) datasets agree: sorting only
    reorders the WORK, never the (input → solution) pairing (App. E.3)."""
    fam = get_family("darcy", nx=12, ny=12)
    key = jax.random.PRNGKey(2)
    skr = generate_dataset(fam, key, 8,
                           dataclasses.replace(CFG, sort_method=sort_method))
    gm = generate_dataset_baseline(fam, key, 8, KC, precond="jacobi")
    np.testing.assert_allclose(skr.solutions, gm.solutions, rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(skr.inputs, gm.inputs, rtol=1e-12)


def test_fault_injection_and_warm_resume(tmp_path):
    """Preempt datagen mid-sequence; a rerun resumes from the checkpoint
    with the recycle space intact and produces the identical dataset."""
    fam = get_family("poisson", nx=14, ny=14)
    cfg = dataclasses.replace(CFG, ckpt_every=2)
    key = jax.random.PRNGKey(1)

    ref = generate_dataset(fam, key, 8, cfg)  # uninterrupted reference

    gen = SKRGenerator(fam, cfg, ckpt_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="injected datagen fault"):
        gen.generate(key, 8, fail_at=5)
    # restart: resumes at system 5 (not 0) and finishes
    progress = []
    res = SKRGenerator(fam, cfg, ckpt_dir=str(tmp_path)).generate(
        key, 8, progress_cb=lambda p, n: progress.append(p))
    assert progress[0] > 1, "resume must skip completed systems"
    np.testing.assert_allclose(res.solutions, ref.solutions, rtol=1e-6,
                               atol=1e-9)


def test_chunked_parallel_equivalence():
    """App. E.2.2: chunked workers produce the same solutions as the
    single-worker sequence (chunks only affect recycling warm-ups)."""
    fam = get_family("poisson", nx=12, ny=12)
    key = jax.random.PRNGKey(3)
    whole = generate_dataset(fam, key, 8, CFG)
    chunks = generate_dataset_chunked(fam, key, 8, CFG, workers=2)
    assert len(chunks) == 2
    got = {}
    for ch in chunks:
        for pos, i in enumerate(ch.order.tolist()):
            got[i] = ch.solutions[pos]
    for i in range(8):
        np.testing.assert_allclose(got[i], whole.solutions[i], rtol=1e-5,
                                   atol=1e-8)


def test_sorting_reduces_chain_length_in_pipeline():
    fam = get_family("helmholtz", nx=12, ny=12)
    res_sorted = generate_dataset(fam, jax.random.PRNGKey(0), 12, CFG)
    res_none = generate_dataset(
        fam, jax.random.PRNGKey(0), 12,
        dataclasses.replace(CFG, sort_method="none"))
    assert res_sorted.chain_len <= res_none.chain_len


def test_recycle_snapshots_recorded():
    fam = get_family("poisson", nx=12, ny=12)
    cfg = dataclasses.replace(CFG, record_recycle=True)
    res = generate_dataset(fam, jax.random.PRNGKey(0), 4, cfg)
    assert len(res.recycle_snapshots) >= 3
    idx, u = res.recycle_snapshots[-1]
    assert u.shape[0] == 144 and u.shape[1] <= KC.k
