"""Pallas TPU kernels for the compute hot spots (DESIGN §4):

  stencil_matvec — variable-coefficient 5-point stencil SpMV (solver inner loop)
  dia_spmv       — banded/diagonal-format SpMV (general flattened operators)
  fused_orthog   — fused CGS2 Gram-Schmidt (Arnoldi orthogonalization)
  arnoldi_step   — one fused (deflated) Arnoldi inner iteration

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling, a jit'd
dispatch wrapper in ops.py, and a pure-jnp oracle in ref.py. `ops.py`
decides from the backend how a kernel runs: compiled on a TPU (fp32 only),
interpreted on the CPU (tests/test_kernels.py sweeps shapes × dtypes
against the oracles; tests/test_tpu_compile.py compiles the main-path
kernels for a described v5e chip).
"""
