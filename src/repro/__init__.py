# repro — a JAX implementation of "Accelerating Data Generation for Neural
# Operators via Krylov Subspace Recycling" (SKR, ICLR 2024): a datagen system
# (pde, core, solvers, kernels, obs) that sorts, chains and recycle-solves
# families of PDE systems into labelled datasets, plus the neural-operator
# consumer that trains on them (operators, train).
#
# The solvers hold residuals of record, refinement and the Krylov cycles in
# fp64 to reach the paper's tolerances (down to 1e-11) with PETSc's
# semantics; JAX's default would silently demote those arrays to fp32, so
# x64 is enabled for the process. Code that wants fp32 (the fp32 inner
# cycles, the Pallas kernels, the FNO) passes its dtype explicitly.
import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"
