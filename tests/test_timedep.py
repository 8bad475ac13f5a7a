"""Time-dependent trajectory subsystem: θ-scheme correctness (order of
accuracy vs the exact heat-equation decay), recycled-vs-cold per-step
solution equivalence, lockstep-vs-sequential trajectory equivalence with
padding, checkpoint/resume, and the registry plumbing — the trajectory-level
extension of the tests/test_batched_solver.py patterns.

Stepping-stack coverage (PR 5): BDF2 2nd-order convergence, mass-matrix-
aware step assembly (M + βΔtL, dense oracle), wave-family energy
boundedness, adaptive-Δt efficiency vs fixed stepping, phase-masked
adaptive lockstep == sequential (fp64 and fp32-inner), and a bitwise anchor
pinning the classic fixed-Δt path to the pre-stack marching loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sorting import sort_features
from repro.core.trajectory import (TrajConfig, TrajectoryGenerator,
                                   generate_trajectories,
                                   generate_trajectories_baseline,
                                   generate_trajectories_chunked,
                                   march_trajectory)
from repro.pde.dia import Stencil5, stencil5_matvec
from repro.pde.registry import (get_timedep_family, list_timedep_families)
from repro.pde.timedep import (AdaptConfig, HeatTimeFamily, MassMatrix,
                               TrajectorySpec, WaveTimeFamily,
                               assemble_diffusion_stencil, quantize_sig)
from repro.solvers.gcrodr import GCRODRSolver
from repro.solvers.operator import PreconditionedOp, StencilOp
from repro.solvers.precond import make_preconditioner
from repro.solvers.types import KrylovConfig

# same budget rationale as test_batched_solver.KC: tol 1e-9 keeps the
# batched-vs-sequential float-reassociation drift under the 1e-8 assertions
KC = KrylovConfig(m=30, k=10, tol=1e-9, maxiter=6000)
CFG = TrajConfig(krylov=KC, precond="jacobi")


# ------------------------------------------------------------------ registry

def test_registry_lists_timedep_families():
    fams = list_timedep_families()
    assert "heat" in fams and "convdiff-t" in fams
    for name in fams:
        fam = get_timedep_family(name, nx=8, ny=8, nt=2)
        assert fam.nt == 2 and fam.nx == 8
    with pytest.raises(KeyError):
        get_timedep_family("nope")


# ----------------------------------------------------------- θ-scheme order

def _eig_decay_error(theta: float, nt: int, t_end: float, nx: int = 12):
    """March the σ=0 heat family (K ≡ 1 ⇒ L is the exact 5-point Laplacian)
    from a discrete Laplacian EIGENVECTOR IC and return the error of the
    final field against the exact semi-discrete decay e^{−λT} v."""
    fam = HeatTimeFamily(nx=nx, ny=nx, nt=nt, dt=t_end / nt, theta=theta,
                         sigma=0.0)
    h = 1.0 / (nx + 1)
    x = h * jnp.arange(1, nx + 1, dtype=jnp.float64)
    v = jnp.sin(jnp.pi * x)[:, None] * jnp.sin(jnp.pi * x)[None, :]
    lam = 2.0 * (4.0 / h**2) * np.sin(np.pi * h / 2.0) ** 2

    spec = fam.sample_spec(jax.random.PRNGKey(0))
    spec = dataclasses.replace(spec, u0=v)
    cfg = TrajConfig(krylov=dataclasses.replace(KC, tol=1e-12),
                     precond="jacobi")
    traj, stats = march_trajectory(fam, spec, cfg)
    assert stats.num_converged == nt

    # the θ-scheme ON an eigenvector is exactly ρ^nt with
    # ρ = (1 − (1−θ)Δtλ) / (1 + θΔtλ) — pin the assembled stepper to it
    dt = t_end / nt
    rho = (1.0 - (1.0 - theta) * dt * lam) / (1.0 + theta * dt * lam)
    np.testing.assert_allclose(traj[-1], rho**nt * np.asarray(v),
                               rtol=1e-7, atol=1e-10)

    exact = np.exp(-lam * t_end) * np.asarray(v)
    return float(np.linalg.norm(traj[-1] - exact))


@pytest.mark.parametrize("theta,expected_order", [(1.0, 1), (0.5, 2)])
def test_theta_scheme_order_of_accuracy(theta, expected_order):
    """Halving Δt divides the temporal error by ~2 (backward Euler) or ~4
    (Crank–Nicolson) against the exact heat-equation decay."""
    t_end = 0.05
    e1 = _eig_decay_error(theta, nt=4, t_end=t_end)
    e2 = _eig_decay_error(theta, nt=8, t_end=t_end)
    ratio = e1 / max(e2, 1e-300)
    lo, hi = (1.6, 2.6) if expected_order == 1 else (3.2, 5.2)
    assert lo <= ratio <= hi, (theta, e1, e2, ratio)


# ----------------------------------------------------- dataset + step validity

@pytest.mark.parametrize("inner_dtype", ["float64", "float32"])
@pytest.mark.parametrize("engine", ["sequential", "batched"])
@pytest.mark.parametrize("name", ["heat", "convdiff-t"])
def test_trajectories_solve_their_step_systems(name, engine, inner_dtype):
    """Every emitted field actually satisfies its implicit-step linear
    system to solver tolerance (the trajectory analogue of the SKR
    dataset-validity test), on both engines and both inner precisions."""
    fam = get_timedep_family(name, nx=10, ny=10, nt=3)
    cfg = dataclasses.replace(
        CFG, krylov=dataclasses.replace(KC, inner_dtype=inner_dtype))
    chunks = generate_trajectories_chunked(fam, jax.random.PRNGKey(0), 3,
                                           cfg, workers=2, engine=engine)
    assert len(chunks) == 2
    order = np.concatenate([ch.order for ch in chunks])
    assert sorted(order.tolist()) == [0, 1, 2]
    assert sum(ch.stats.num_converged for ch in chunks) == 9
    assert sum(ch.stats.num for ch in chunks) == 9

    specs = fam.sample_specs(jax.random.PRNGKey(0), 3)
    step1 = fam.step_fn()
    for ch in chunks:
        assert ch.trajectories.shape == (len(ch.order), 4, 10, 10)
        assert np.isfinite(ch.trajectories).all()
        for pos, i in enumerate(ch.order.tolist()):
            traj = ch.trajectories[pos]
            lat = jax.tree_util.tree_map(lambda a: a[i], specs.latent)
            np.testing.assert_array_equal(traj[0], np.asarray(specs.u0[i]))
            for s in range(fam.nt):
                a, b = step1(lat, jnp.asarray(traj[s]), s * fam.dt,
                             (s + 1) * fam.dt)
                r = np.asarray(b) - np.asarray(
                    stencil5_matvec(a, jnp.asarray(traj[s + 1])))
                assert (np.linalg.norm(r)
                        <= KC.tol * np.linalg.norm(np.asarray(b)) * 1.1), \
                    (i, s)


@pytest.mark.parametrize("name", ["heat", "convdiff-t", "wave"])
def test_recycled_matches_cold_start_per_step(name):
    """Recycling changes the WORK, never the solutions: per-step fields from
    the GCRO-DR carry chain match the cold-start GMRES baseline to solver
    tolerance, at no more total Krylov iterations."""
    fam = get_timedep_family(name, nx=10, ny=10, nt=4)
    key = jax.random.PRNGKey(1)
    rec = generate_trajectories(fam, key, 3, CFG)
    cold = generate_trajectories_baseline(fam, key, 3, KC, precond="jacobi")
    for i in range(3):
        for s in range(fam.nt + 1):
            a, b = rec.trajectories[i, s], cold.trajectories[i, s]
            rel = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300)
            assert rel <= 1e-6, (i, s, rel)
    # the recycling win (strict win asserted in benchmarks/trajectory_recycle
    # at scale; tiny grids only guarantee "no worse" modulo warm-start QR)
    assert (rec.stats.total_iterations
            <= cold.stats.total_iterations + fam.nt)


# --------------------------------------------------- lockstep engine parity

@pytest.mark.parametrize("name", ["heat", "convdiff-t"])
def test_lockstep_matches_sequential_with_padding(name):
    """batched == sequential chunked engine per trajectory slot, with a
    worker count that does NOT divide num (uneven chunks exercise the
    zero-RHS padding rows)."""
    fam = get_timedep_family(name, nx=10, ny=10, nt=3)
    key = jax.random.PRNGKey(2)
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    bat = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="batched")
    assert len(seq) == len(bat) == 2
    assert {len(c.order) for c in seq} == {2, 3}
    for cs, cb in zip(seq, bat):
        np.testing.assert_array_equal(cs.order, cb.order)
        assert cb.stats.num_converged == len(cb.order) * fam.nt
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.trajectories[pos] - cs.trajectories[pos])
                   / max(np.linalg.norm(cs.trajectories[pos]), 1e-300))
            assert rel <= 1e-8, (pos, rel)


def test_chunked_workers1_bitwise_stable():
    """workers=1 routes through the per-trajectory sequential loop and is
    BITWISE identical to the plain generator on the same key."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3)
    key = jax.random.PRNGKey(3)
    whole = generate_trajectories(fam, key, 4, CFG)
    chunks = generate_trajectories_chunked(fam, key, 4, CFG, workers=1)
    assert len(chunks) == 1
    ch = chunks[0]
    np.testing.assert_array_equal(ch.order, whole.order)
    for pos, i in enumerate(ch.order.tolist()):
        np.testing.assert_array_equal(ch.trajectories[pos],
                                      whole.trajectories[i])


# ------------------------------------------------------------ rhs + resume

def test_increment_rhs_mode_matches_full():
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3)
    key = jax.random.PRNGKey(4)
    full = generate_trajectories(fam, key, 2, CFG)
    inc = generate_trajectories(fam, key, 2,
                                dataclasses.replace(CFG,
                                                    rhs_mode="increment"))
    rel = (np.linalg.norm(full.trajectories - inc.trajectories)
           / np.linalg.norm(full.trajectories))
    assert rel <= 1e-6, rel


def test_fault_injection_and_warm_resume(tmp_path):
    """Preempt datagen mid-sequence (unit = trajectories); a rerun resumes
    from the checkpoint — recycle space intact — and the result is bitwise
    identical to an uninterrupted run."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3)
    cfg = dataclasses.replace(CFG, ckpt_every=1)
    key = jax.random.PRNGKey(5)
    gen = TrajectoryGenerator(fam, cfg, ckpt_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="injected"):
        gen.generate(key, 4, fail_at=2)
    progress = []
    res = TrajectoryGenerator(fam, cfg, ckpt_dir=str(tmp_path)).generate(
        key, 4, progress_cb=lambda p, n: progress.append(p))
    assert progress[0] > 1, "resume must skip completed trajectories"
    plain = generate_trajectories(fam, key, 4, CFG)
    np.testing.assert_array_equal(res.trajectories, plain.trajectories)


# ----------------------------------------------------------------- families

def test_trajectory_spec_shapes():
    for name in list_timedep_families():
        fam = get_timedep_family(name, nx=8, ny=8, nt=2)
        specs = fam.sample_specs(jax.random.PRNGKey(0), 3)
        assert isinstance(specs, TrajectorySpec)
        assert specs.u0.shape == (3, 8, 8)
        assert specs.no_input.shape == (3, 8, 8)
        assert specs.features.ndim == 2 and specs.features.shape[0] == 3
        a, b = fam.step_fn()(
            jax.tree_util.tree_map(lambda x: x[0], specs.latent),
            specs.u0[0], 0.0, fam.dt)
        assert a.shape == (5, 8, 8) and b.shape == (8, 8)
        assert jnp.isfinite(a).all() and jnp.isfinite(b).all()


def test_heat_stencil_is_spd_shifted():
    """A = I + θΔt L must keep a positive diagonal and weak diagonal
    dominance (M-matrix shifted by identity) — the conditioning story the
    θ-scheme module docstring sells."""
    fam = get_timedep_family("heat", nx=8, ny=8, nt=2)
    specs = fam.sample_specs(jax.random.PRNGKey(0), 1)
    lat = jax.tree_util.tree_map(lambda x: x[0], specs.latent)
    a, _ = fam.step_fn()(lat, specs.u0[0], 0.0, fam.dt)
    a = np.asarray(a)
    assert (a[0] > 0).all()                      # center
    off_sum = np.abs(a[1:]).sum(axis=0)
    assert (a[0] >= off_sum - 1e-9).all()        # diagonal dominance


# ======================================================== stepping stack


def _eig_ic(nx):
    """Lowest discrete-Laplacian eigenvector + its decay rate."""
    h = 1.0 / (nx + 1)
    x = h * jnp.arange(1, nx + 1, dtype=jnp.float64)
    v = jnp.sin(jnp.pi * x)[:, None] * jnp.sin(jnp.pi * x)[None, :]
    lam = 2.0 * (4.0 / h**2) * np.sin(np.pi * h / 2.0) ** 2
    return v, lam


def _bdf2_decay_error(nt: int, t_end: float = 0.05, nx: int = 12) -> float:
    """σ=0 heat family under BDF2 (CN bootstrap) from an eigenvector IC:
    error of the final field against the exact semi-discrete decay."""
    fam = HeatTimeFamily(nx=nx, ny=nx, nt=nt, dt=t_end / nt, theta=0.5,
                         sigma=0.0, integrator="bdf2")
    assert not fam.classic and fam.order == 2
    v, lam = _eig_ic(nx)
    spec = dataclasses.replace(fam.sample_spec(jax.random.PRNGKey(0)), u0=v)
    cfg = TrajConfig(krylov=dataclasses.replace(KC, tol=1e-12),
                     precond="jacobi")
    traj, stats = march_trajectory(fam, spec, cfg)
    assert stats.num_converged == nt
    exact = np.exp(-lam * t_end) * np.asarray(v)
    return float(np.linalg.norm(traj[-1] - exact))


def test_bdf2_second_order_convergence():
    """Halving Δt divides the BDF2 temporal error by ~4 against the exact
    heat-equation decay (the order-2 extension of the θ-order test)."""
    e1 = _bdf2_decay_error(nt=8)
    e2 = _bdf2_decay_error(nt=16)
    ratio = e1 / max(e2, 1e-300)
    assert 3.0 <= ratio <= 5.2, (e1, e2, ratio)


# ------------------------------------------------------------ mass matrices

class _MassHeat(HeatTimeFamily):
    """Heat family with the compact mass matrix — exercises the generic
    M + βΔtL step assembly (wave has its own specialized elimination)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._m = MassMatrix.compact(self.nx, self.ny)

    def mass(self):
        return self._m


def test_mass_matrix_compact_is_spd_dominant():
    m = MassMatrix.compact(9, 9)
    c = np.asarray(m.coeffs)
    assert (c[0] > 0).all()
    assert (c[0] >= np.abs(c[1:]).sum(axis=0) - 1e-12).all()
    dense = m.to_dia().to_dense()
    np.testing.assert_allclose(dense, dense.T, atol=1e-14)
    w = np.linalg.eigvalsh(dense)
    assert w.min() > 0.3 and w.max() < 1.0 + 1e-12
    # Stencil5 and DIA exports agree
    np.testing.assert_array_equal(dense, m.as_stencil5().to_dense())
    ident = MassMatrix.identity(5, 5)
    np.testing.assert_array_equal(ident.to_dia().to_dense(), np.eye(25))


def test_mass_aware_step_matches_dense_oracle():
    """The generalized θ-step with M ≠ I assembles exactly
    A = M + θΔtL(t+Δt), b = M u (zero source, backward Euler) — pinned
    against dense algebra."""
    fam = _MassHeat(nx=8, ny=8, nt=2, dt=3e-3, theta=1.0)
    assert not fam.classic
    spec = fam.sample_spec(jax.random.PRNGKey(1))
    state = fam.init_state(spec)
    a, b = fam.build_fn()(spec.latent, state, 0.0, fam.dt, fam.dt, True)
    m_dense = fam.mass().to_dia().to_dense()
    l_dense = Stencil5(fam.spatial_coeffs(spec.latent, fam.dt)).to_dense()
    np.testing.assert_allclose(Stencil5(a).to_dense(),
                               m_dense + fam.dt * l_dense,
                               rtol=1e-13, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(b).reshape(-1),
        m_dense @ np.asarray(spec.u0).reshape(-1), rtol=1e-13, atol=1e-13)


def test_mass_family_lockstep_matches_sequential():
    """Generic mass-matrix stepping through the full engines: batched ==
    sequential per trajectory slot (uneven chunks → padding)."""
    fam = _MassHeat(nx=10, ny=10, nt=3, dt=3e-3)
    key = jax.random.PRNGKey(8)
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    bat = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="batched")
    for cs, cb in zip(seq, bat):
        np.testing.assert_array_equal(cs.order, cb.order)
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.trajectories[pos] - cs.trajectories[pos])
                   / max(np.linalg.norm(cs.trajectories[pos]), 1e-300))
            assert rel <= 1e-8, (pos, rel)


# ------------------------------------------------------------- wave family

def _march_states_dense(fam, spec, nsteps):
    """March the generalized stack with DENSE solves (exact linear algebra,
    no Krylov noise) — the integrator-property oracle."""
    state = fam.init_state(spec)
    build1, eval1 = fam.build_fn(), fam.eval_fn()
    t, dt = 0.0, fam.dt
    states = [state]
    for i in range(nsteps):
        boot = i == 0
        a, b = build1(spec.latent, state, t, dt, dt, boot)
        x = np.linalg.solve(Stencil5(a).to_dense(),
                            np.asarray(b).reshape(-1)).reshape(fam.nx, fam.ny)
        state, _ = eval1(spec.latent, state, jnp.asarray(x), t, dt, dt, dt,
                         boot, i >= 2)
        t += dt
        states.append(state)
    return states


def test_wave_energy_bounded_over_rollout():
    """Discrete energy ½(vᵀMv + uᵀKu): conserved to ~machine precision by
    the trapezoid wave stepper, bounded (mildly dissipative) under BDF2."""
    for integrator, tol_growth in (("theta", 1e-10), ("bdf2", 1e-10)):
        fam = WaveTimeFamily(nx=12, ny=12, nt=25, dt=2e-3,
                             integrator=integrator)
        spec = fam.sample_spec(jax.random.PRNGKey(3))
        states = _march_states_dense(fam, spec, fam.nt)
        energies = [float(fam.energy(spec.latent, s)) for s in states]
        e0 = energies[0]
        assert e0 > 0.0
        assert max(energies) <= e0 * (1.0 + tol_growth), integrator
        # no spurious blow-down either: the rollout keeps real energy
        assert min(energies) >= e0 * (0.5 if integrator == "bdf2" else
                                      1.0 - 1e-10), integrator


def test_wave_trapezoid_energy_exact_conservation():
    fam = WaveTimeFamily(nx=10, ny=10, nt=30, dt=3e-3, theta=0.5)
    spec = fam.sample_spec(jax.random.PRNGKey(9))
    states = _march_states_dense(fam, spec, fam.nt)
    e = np.array([float(fam.energy(spec.latent, s)) for s in states])
    assert np.abs(e - e[0]).max() / e[0] <= 1e-9


def test_wave_family_registry_and_mass():
    fams = list_timedep_families()
    assert "wave" in fams
    fam = get_timedep_family("wave", nx=8, ny=8, nt=2)
    assert isinstance(fam, WaveTimeFamily)
    assert fam.mass() is not None and not fam.classic
    specs = fam.sample_specs(jax.random.PRNGKey(0), 3)
    assert specs.u0.shape == (3, 8, 8)
    res = generate_trajectories(fam, jax.random.PRNGKey(0), 2, CFG)
    assert res.trajectories.shape == (2, 3, 8, 8)
    assert np.isfinite(res.trajectories).all()
    assert res.stats.num_converged == res.stats.num == 4


def test_wave_lockstep_matches_sequential():
    fam = get_timedep_family("wave", nx=10, ny=10, nt=3, dt=2e-3)
    key = jax.random.PRNGKey(2)
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    bat = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="batched")
    for cs, cb in zip(seq, bat):
        np.testing.assert_array_equal(cs.order, cb.order)
        assert cs.stats.num == cb.stats.num
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.trajectories[pos] - cs.trajectories[pos])
                   / max(np.linalg.norm(cs.trajectories[pos]), 1e-300))
            assert rel <= 1e-7, (pos, rel)


# ------------------------------------------------------------- adaptive Δt

class _DriftEquilibHeat(HeatTimeFamily):
    """Forced heat with a SHARP mid-window conductivity switch: u tracks the
    moving equilibrium L(t)⁻¹φ, so all the dynamics (and all the temporal
    error) concentrate in the switch window — the workload where adaptive
    stepping beats any uniform Δt."""

    def spatial_coeffs(self, latent, t):
        g0, g1 = latent
        s = jax.nn.sigmoid((t / self.t_end - 0.5) * 80.0)
        k = jnp.exp(self.sigma * ((1.0 - s) * g0 + s * g1))
        return assemble_diffusion_stencil(k, self.hx, self.hy)

    def source(self, latent, t):
        nx = self.nx
        h = 1.0 / (nx + 1)
        x = h * jnp.arange(1, nx + 1, dtype=jnp.float64)
        return 50.0 * jnp.sin(jnp.pi * x)[:, None] * jnp.sin(jnp.pi * x)[None, :]

    def sample_specs(self, key, num):
        keys = jax.random.split(key, num)
        specs = []
        for k in keys:
            sp = self.sample_spec(k)
            g0, _ = sp.latent
            a0 = assemble_diffusion_stencil(jnp.exp(self.sigma * g0),
                                            self.hx, self.hy)
            u0 = np.linalg.solve(
                Stencil5(a0).to_dense(),
                np.asarray(self.source(None, 0.0)).reshape(-1))
            specs.append(dataclasses.replace(
                sp, u0=jnp.asarray(u0.reshape(self.nx, self.ny))))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *specs)


def _drift_fam(nt, **kw):
    return _DriftEquilibHeat(nx=10, ny=10, nt=nt, dt=1.0 / nt, sigma=0.8,
                             **kw)


@pytest.mark.slow
def test_adaptive_beats_fixed_on_stiff_drift():
    """Adaptive Δt reaches a given accuracy with FEWER steps than uniform
    stepping on a stiff conductivity drift: at the adaptive run's accepted
    step count, fixed-Δt backward Euler has ≥2x the error (so matching the
    adaptive tolerance costs it strictly more steps)."""
    cfg = TrajConfig(krylov=dataclasses.replace(KC, tol=1e-10),
                     precond="jacobi")
    key = jax.random.PRNGKey(7)
    nref = 512
    ref = generate_trajectories(_drift_fam(nref, theta=0.5), key, 1, cfg)
    ridx = np.linspace(0, nref, 5).astype(int)
    refs = [ref.trajectories[0][i] for i in ridx]

    ra = generate_trajectories(
        _drift_fam(4, theta=1.0,
                   adapt=AdaptConfig(step_tol=1e-3, fac_max=6.0)),
        key, 1, cfg)
    na = ra.stats.num - ra.stats.num_rejected
    assert ra.stats.num_rejected >= 1          # the controller did reject
    assert na < 120                            # and did stretch steps
    err_a = max(np.linalg.norm(ra.trajectories[0][i] - refs[i])
                / np.linalg.norm(refs[i]) for i in range(1, 5))

    rf = generate_trajectories(_drift_fam(int(na), theta=1.0), key, 1, cfg)
    q = [int(round(f * na / 4)) for f in range(5)]
    err_f = max(np.linalg.norm(rf.trajectories[0][q[i]] - refs[i])
                / np.linalg.norm(refs[i]) for i in range(1, 5))
    assert err_f >= 2.0 * err_a, (na, err_a, err_f)


def test_adaptive_lockstep_matches_sequential():
    """Phase-masked adaptive lockstep == sequential per trajectory slot —
    identical Δt paths (quantized controller), identical solve/reject
    counts, solutions to tolerance. Uneven chunks exercise the zero-RHS
    phase padding."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3, dt=2e-2,
                             adapt=AdaptConfig(step_tol=2e-3))
    key = jax.random.PRNGKey(2)
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    bat = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="batched")
    assert {len(c.order) for c in bat} == {2, 3}
    for cs, cb in zip(seq, bat):
        np.testing.assert_array_equal(cs.order, cb.order)
        assert cs.stats.num == cb.stats.num
        assert cs.stats.num_rejected == cb.stats.num_rejected
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.trajectories[pos] - cs.trajectories[pos])
                   / max(np.linalg.norm(cs.trajectories[pos]), 1e-300))
            assert rel <= 1e-7, (pos, rel)


def test_adaptive_lockstep_fp32_inner_matches_sequential_fp64():
    """The adaptive lockstep under inner_dtype="float32" still matches the
    fp64 sequential engine: labels are fp64 at tol, and the quantized
    controller absorbs the fp32 engine's (tol-level) solution noise, so
    even the step sequences agree."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3, dt=2e-2,
                             adapt=AdaptConfig(step_tol=2e-3))
    key = jax.random.PRNGKey(2)
    cfg32 = TrajConfig(krylov=dataclasses.replace(KC, inner_dtype="float32"),
                       precond="jacobi")
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    b32 = generate_trajectories_chunked(fam, key, 5, cfg32, workers=2,
                                        engine="batched")
    for cs, cb in zip(seq, b32):
        assert cs.stats.num == cb.stats.num
        assert cb.stats.total_outer_refinements >= 1
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.trajectories[pos] - cs.trajectories[pos])
                   / max(np.linalg.norm(cs.trajectories[pos]), 1e-300))
            assert rel <= 1e-6, (pos, rel)


def test_adaptive_wave_lockstep_matches_sequential():
    """Adaptive Δt on the M ≠ I wave family: phase-masked lockstep ==
    sequential (fp64 engine and fp32-inner engine), identical step
    sequences — the acceptance-criteria pairing of adaptivity with the
    mass-matrix family."""
    fam = get_timedep_family("wave", nx=10, ny=10, nt=3, dt=5e-3,
                             adapt=AdaptConfig(step_tol=2e-3))
    key = jax.random.PRNGKey(3)
    cfg32 = TrajConfig(krylov=dataclasses.replace(KC, inner_dtype="float32"),
                       precond="jacobi")
    seq = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="sequential")
    bat = generate_trajectories_chunked(fam, key, 5, CFG, workers=2,
                                        engine="batched")
    b32 = generate_trajectories_chunked(fam, key, 5, cfg32, workers=2,
                                        engine="batched")
    for cs, cb, c3 in zip(seq, bat, b32):
        np.testing.assert_array_equal(cs.order, cb.order)
        assert cs.stats.num == cb.stats.num == c3.stats.num
        assert cs.stats.num_rejected == cb.stats.num_rejected
        for pos in range(len(cs.order)):
            nrm = max(np.linalg.norm(cs.trajectories[pos]), 1e-300)
            r64 = np.linalg.norm(cb.trajectories[pos]
                                 - cs.trajectories[pos]) / nrm
            r32 = np.linalg.norm(c3.trajectories[pos]
                                 - cs.trajectories[pos]) / nrm
            assert r64 <= 1e-7 and r32 <= 1e-6, (pos, r64, r32)


def test_adaptive_budget_exhaustion_freezes_consistently():
    """A trajectory that exhausts max_steps freezes (remaining save points
    repeat the last field) — identically in both engines."""
    fam = get_timedep_family("heat", nx=8, ny=8, nt=4, dt=2e-2,
                             adapt=AdaptConfig(step_tol=1e-4, max_steps=3))
    key = jax.random.PRNGKey(4)
    seq = generate_trajectories_chunked(fam, key, 3, CFG, workers=1)
    bat = generate_trajectories_chunked(fam, key, 3, CFG, workers=3,
                                        engine="batched")
    for cb in bat:
        for pos, i in enumerate(cb.order.tolist()):
            src = int(np.nonzero(seq[0].order == i)[0][0])
            np.testing.assert_allclose(cb.trajectories[pos],
                                       seq[0].trajectories[src],
                                       rtol=1e-7, atol=1e-12)
    # frozen tail: with a 3-solve budget against 4 required saves, every
    # trajectory ends in repeated (frozen) save points
    for tr in seq[0].trajectories:
        assert np.array_equal(tr[-1], tr[-2])


def test_controller_respects_dt_clamps():
    """The save-time stretch never violates dt_max, and a failing step
    already at the dt_min floor is accepted (no rejection death spiral)."""
    from repro.pde.timedep import PIStepController

    cfg = AdaptConfig(step_tol=1e-3, dt_min=1e-3, dt_max=5e-3)
    ctrl = PIStepController(cfg, order=1, dt0=5e-3)
    assert ctrl.propose(6e-3) == 5e-3     # remaining beyond cap: no stretch
    assert ctrl.propose(4.9e-3) == 4.9e-3  # within cap: land exactly
    ctrl.dt = cfg.dt_min
    assert ctrl.decide(1e-1, cfg.dt_min) is True   # floor accept
    assert ctrl.naccept == 1
    # and an accepted step's growth stays inside [dt_min, dt_max]
    assert cfg.dt_min <= ctrl.dt <= cfg.dt_max

    # a tiny save-boundary landing step must NOT collapse the controller:
    # growth resumes from the controller's own step, not the clip
    ctrl2 = PIStepController(cfg, order=1, dt0=5e-3)
    assert ctrl2.decide(1e-5, 5e-5) is True        # clipped landing accept
    assert ctrl2.dt == cfg.dt_max                  # straight back to cap
    assert ctrl2.dt_prev == 5e-5                   # BDF2 ρ uses actual step


def test_controller_never_reproposes_rejected_step():
    """Marginal-rejection livelock guard: after a rejection whose shrink
    factor exceeds 1/1.25, the save-boundary stretch must NOT re-propose
    the exact step size that was just rejected — the estimate is
    deterministic per position, so re-trying it can never succeed."""
    from repro.pde.timedep import PIStepController

    ctrl = PIStepController(AdaptConfig(step_tol=2e-3), order=2, dt0=2e-3)
    remaining = 2.1e-3
    dt1 = ctrl.propose(remaining)
    assert dt1 == remaining                      # stretched to the boundary
    assert ctrl.decide(2.2e-3, dt1) is False     # marginal reject (fac~0.87)
    dt2 = ctrl.propose(remaining)
    assert dt2 < dt1                             # never the rejected size
    assert ctrl.decide(1e-3, dt2) is True        # smaller step lands
    # rejection memory is cleared on accept: stretching works again
    assert ctrl.dt_bad == float("inf")


def test_wave_step_includes_forcing():
    """A wave subclass overriding source() gets the eliminated forcing term
    (θΔt²(θf_new + (1−θ)f_old)) in its step rhs — not silently dropped."""
    class _ForcedWave(WaveTimeFamily):
        def source(self, latent, t):
            return jnp.ones((self.nx, self.ny), jnp.float64)

    kw = dict(nx=6, ny=6, nt=2, dt=1e-2, theta=0.5)
    fam_f = _ForcedWave(**kw)
    fam_0 = WaveTimeFamily(**kw)
    spec = fam_f.sample_spec(jax.random.PRNGKey(0))
    state = fam_f.init_state(spec)
    a_f, b_f = fam_f.build_fn()(spec.latent, state, 0.0, 1e-2, 1e-2, True)
    a_0, b_0 = fam_0.build_fn()(spec.latent, state, 0.0, 1e-2, 1e-2, True)
    np.testing.assert_array_equal(np.asarray(a_f), np.asarray(a_0))
    np.testing.assert_allclose(np.asarray(b_f - b_0),
                               0.5 * 1e-4 * np.ones((6, 6)),
                               rtol=1e-10, atol=1e-15)


def test_quantize_sig():
    assert quantize_sig(0.123456) == 0.12
    assert quantize_sig(3.456e-7) == 3.5e-7
    assert quantize_sig(0.0) == 0.0
    assert quantize_sig(float("inf")) == float("inf")
    # the guard property: tol-level perturbations do not move the value
    assert quantize_sig(1.0000000012e-3) == quantize_sig(1.0e-3)


# ------------------------------------------------- classic-path bitwise pin

def test_classic_fixed_dt_path_bitwise_anchor():
    """The fixed-Δt θ-scheme path must stay BITWISE-identical to the
    original (pre-stepping-stack) marching loop: recompute the generator's
    output with a verbatim transcription of that loop and require exact
    equality. Reroute the classic path through the generalized stack and
    this fails."""
    fam = get_timedep_family("heat", nx=10, ny=10, nt=3)
    assert fam.classic
    key = jax.random.PRNGKey(6)
    res = generate_trajectories(fam, key, 3, CFG)

    specs = fam.sample_specs(key, 3)
    order = sort_features(np.asarray(specs.features), CFG.sort_method)
    np.testing.assert_array_equal(res.order, order)
    solver = GCRODRSolver(CFG.krylov, use_kernel=CFG.use_kernel)
    step1 = fam.step_fn()
    for i in order.tolist():
        lat = jax.tree_util.tree_map(lambda a: a[i], specs.latent)
        u = jnp.asarray(specs.u0[i])
        np.testing.assert_array_equal(res.trajectories[i, 0], np.asarray(u))
        for step in range(fam.nt):
            a, b = step1(lat, u, step * fam.dt, (step + 1) * fam.dt)
            st5 = Stencil5(a)
            pre = make_preconditioner(CFG.precond, st5,
                                      use_kernel=CFG.use_kernel)
            op = PreconditionedOp(StencilOp(st5.coeffs, CFG.use_kernel), pre)
            x, _ = solver.solve(op, np.asarray(b).reshape(-1))
            u = jnp.asarray(np.asarray(x).reshape(fam.nx, fam.ny))
            np.testing.assert_array_equal(res.trajectories[i, step + 1],
                                          np.asarray(u))
