"""The plain reference, at tiny sizes."""
import jax
import numpy as np

from bench import reference


def test_reference_matches_program_assembly():
    from repro.pde.registry import get_family

    fam = get_family("darcy", nx=12, ny=10)
    batch = fam.sample_batch(jax.random.PRNGKey(1), 3)
    a, b = reference.darcy_system(np.asarray(batch.no_input), fam.source)
    np.testing.assert_allclose(a, np.asarray(batch.op.coeffs), rtol=1e-13)
    np.testing.assert_array_equal(b, np.asarray(batch.b))


def test_reference_passes_exact_and_flags_perturbed_labels():
    rng = np.random.default_rng(0)
    a, b = reference.darcy_system(np.exp(rng.normal(size=(2, 16, 16))), 1.0)
    u = reference.solve(a, b, np.float64)
    assert reference.stencil_residual(a, u, b).max() < 1e-12
    bad = u.copy()
    bad[1, 3, 4] *= 1.0 + 1e-5
    res = reference.stencil_residual(a, bad, b)
    assert res[0] < 1e-12 and res[1] > 1e-8
    # float32 solves miss tol = 1e-8 by far: the control's premise
    assert reference.stencil_residual(
        a, reference.solve(a, b, np.float32), b).min() > 1e-7
