"""The main-path Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described topology, so
these tests catch what the CPU interpreter cannot (block tiling, int32
index maps, VMEM limits, ops Mosaic does not lower). Each kernel compiles
as the lockstep solver calls it — vmapped over 8 chains, fp32 — at the
families' default grid (64x64) and at 256x256 (n = 65,536, near the
paper's largest systems). The topology is described inside a fixture, so
only the worker that runs this file loads the TPU library.

Also here: the lockstep solver's fp64 cycle programs, which leave the
harmonic-Ritz eigensolve to the host, and its refresh programs; how the
backend steers the kernel path (`kernels/ops.py`), and where the
compile cache lands (`repro.compile_cache`).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, M1, K, KP1 = 8, 41, 15, 9     # chains, m + 1, k, expansion fan-out
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_case(name, nx, sds):
    n = nx * nx
    if name == "stencil5_matvec":
        from repro.kernels.stencil_matvec import stencil5_matvec_pallas

        fn = jax.vmap(lambda c, x: stencil5_matvec_pallas(c, x,
                                                          interpret=False))
        return fn, (sds(B, 5, nx, nx), sds(B, nx, nx))
    if name == "fused_orthog":
        from repro.kernels.fused_orthog import fused_orthog_pallas

        fn = jax.vmap(lambda v, w, m: fused_orthog_pallas(v, w, m,
                                                          interpret=False))
        return fn, (sds(B, M1, n), sds(B, n), sds(B, M1))
    if name == "arnoldi_step":
        from repro.kernels.arnoldi_step import arnoldi_step_pallas

        fn = jax.vmap(lambda *a: arnoldi_step_pallas(*a, interpret=False))
        return fn, (sds(B, 5, nx, nx), sds(B, n), sds(B, K, n),
                    sds(B, M1, n), sds(B, n), sds(B, M1))
    from repro.kernels.dia_spmv import dia_spmv_strided_pallas

    offsets = (-nx, -1, 0, 1, nx)   # Stencil5.to_dia, as expansion calls it

    def fn(data, x):
        return dia_spmv_strided_pallas(offsets, data, x, op_stride=KP1,
                                       interpret=False)
    return fn, (sds(B, 5, n), sds(B * KP1, n))


@pytest.mark.parametrize("nx", [64, 256])
@pytest.mark.parametrize("kernel", ["stencil5_matvec", "fused_orthog",
                                    "arnoldi_step", "dia_spmv_strided"])
def test_kernel_compiles_for_v5e(kernel, nx, one_chip):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)

    fn, args = _kernel_case(kernel, nx, sds)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert kernel.split("_strided")[0] in hlo   # the kernel's stable name


@pytest.mark.parametrize("dtype,acc", [(jnp.float64, None),
                                       (jnp.float32, jnp.float64)],
                         ids=["fp64_storage", "fp64_cgs2_acc"])
def test_fp64_kernel_request_on_tpu_raises(dtype, acc, monkeypatch):
    """On a TPU an fp64 kernel request is an error, never a quiet jnp
    fallback (Mosaic has no fp64)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nx, n = 8, 64
    args = (jnp.ones((5, nx, nx), dtype), jnp.ones((n,), dtype),
            jnp.ones((2, n), dtype), jnp.ones((M1, n), dtype),
            jnp.ones((n,), dtype), jnp.ones((M1,), dtype))
    with pytest.raises(TypeError, match="fp32 on a TPU"):
        ops.arnoldi_step(*args, use_kernel=True, acc_dtype=acc)


def test_backend_decides_interpret(monkeypatch):
    assert ops.interpret_mode(jnp.float64) is True          # CPU: interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode(jnp.float32) is False         # TPU: compiled
    assert not ops.kernels_take(jnp.float64)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        ops.interpret_mode(jnp.float32)


@pytest.mark.parametrize("field", ["inner_dtype", "cgs2_acc"])
def test_fp64_solver_request_on_tpu_raises(field, monkeypatch):
    """A kernel solver built on a TPU from a config with fp64 inner storage
    or fp64 CGS2 accumulation raises at construction; the fp32 kernel
    config and the fp64 jnp config are accepted."""
    from repro.solvers.batched import BatchedGCRODRSolver
    from repro.solvers.gcrodr import GCRODRSolver
    from repro.solvers.gmres import solve_gmres
    from repro.solvers.types import KrylovConfig

    kw = {"inner_dtype": "float32", field: "float64"}
    cfg = KrylovConfig(**kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for make in (GCRODRSolver, BatchedGCRODRSolver):
        with pytest.raises(TypeError, match="fp32 on a TPU"):
            make(cfg, use_kernel=True)
        make(cfg, use_kernel=False)
        make(KrylovConfig(inner_dtype="float32"), use_kernel=True)
    with pytest.raises(TypeError, match="fp32 on a TPU"):
        solve_gmres(None, jnp.ones((4, 4)), cfg, use_kernel=True)


def _entry_args(bsz, nx=64, k=15, dt=jnp.float64, use_kernel=False):
    """Abstract arguments of the lockstep solver's `_entry` for `bsz`
    chains at n = nx² in `dt` (a kernel solver's operator when
    `use_kernel`)."""
    from repro.solvers.operator import PreconditionedOp, StencilOp
    from repro.solvers.precond import JacobiPrecond

    n = nx * nx

    def sds(*shape, d=dt):
        return jax.ShapeDtypeStruct(shape, d)

    ops = PreconditionedOp(StencilOp(sds(bsz, 5, nx, nx),
                                     use_kernel=use_kernel),
                           JacobiPrecond(sds(bsz, n)))
    vec, basis, mask = sds(bsz, n), sds(bsz, n, k), sds(bsz, d=bool)
    return (ops, vec, vec, basis, basis, basis, mask, mask, sds(),
            sds(d=jnp.int32), sds())


def _cycle_shapes(bsz, nx=64, m=40, k=15, dt=jnp.float64, use_kernel=False,
                  stall_break=False):
    """Abstract operators, state and aux of the lockstep solver at n = nx²
    (GCRO-DR m 40, k 15; darcy-64-f64's shapes by default) for `bsz`
    chains, fp64 on jnp unless told otherwise."""
    from repro.solvers import batched as bt

    args = _entry_args(bsz, nx, k, dt, use_kernel)
    s, aux, _ = jax.eval_shape(lambda *a: bt._entry(
        *a, k=k, use_carry=True, pad_given=True, stall_break=stall_break),
        *args)
    return args[0], s, aux


def _cycle_kw(name, m=40, k=15, use_kernel=False, stall_break=False):
    kw = dict(k=k, orthog="cgs2", use_kernel=use_kernel, h_acc="native",
              stall_break=stall_break)
    return (dict(kw, m=m, can_grow=False) if name == "fresh"
            else dict(kw, mi=m - k))


def _cycle_fn(name):
    from repro.solvers import batched as bt

    return bt._fresh_cycle if name == "fresh" else bt._deflated_cycle


@pytest.mark.parametrize("program", ["fresh", "deflated", "fresh_refresh",
                                     "deflated_refresh"])
def test_fp64_host_eig_programs_compile_for_v5e(program, one_chip):
    """The fp64 cycle programs that end at the harmonic-Ritz pencil, and
    the refresh programs that rebuild (C, U) from the host's basis, compile
    for one v5e chip at darcy-64-f64's shapes and 64 chains, and fit its
    memory."""
    assert 0 < _bytes(_compiled(program, 64, one_chip)) < 16e9   # 16 GB


def _compiled(program, bsz, one_chip, nx=64, dt=jnp.float64,
              use_kernel=False):
    """One lockstep program compiled for a described v5e at `bsz`
    chains."""
    from repro.solvers import batched as bt

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    if program == "entry":
        lowered = bt._entry.lower(
            *place(_entry_args(bsz, nx, dt=dt, use_kernel=use_kernel)),
            k=15, use_carry=True, pad_given=True,
            stall_break=dt == jnp.float32)
        return lowered.compile()
    name = program.split("_")[0]
    # the refinement driver's fp32 solver breaks off a stalled pass; its
    # fp64 fallback solver does not
    brk = dt == jnp.float32
    fn, kw = _cycle_fn(name), _cycle_kw(name, use_kernel=use_kernel,
                                        stall_break=brk)
    ops, s, aux = _cycle_shapes(bsz, nx, dt=dt, use_kernel=use_kernel,
                                stall_break=brk)
    if program == name:
        return fn.lower(place(ops), place(s), place(aux), **kw).compile()
    s, _, pend = jax.eval_shape(lambda *a: fn(*a, **kw), ops, s, aux)
    k, width = kw["k"], kw.get("m", kw.get("mi", 0) + kw["k"])
    p = jax.ShapeDtypeStruct((bsz, width, k), dt)
    mask = jax.ShapeDtypeStruct((bsz,), bool)
    if name == "fresh":
        q = jax.ShapeDtypeStruct((bsz, width + 1, k), dt)
        inv = jax.ShapeDtypeStruct((bsz, k, k), dt)
        lowered = bt._fresh_refresh.lower(
            *place((s, pend["v"], pend["h"], p, q, inv, mask)), k=k)
    else:
        lowered = bt._deflated_refresh.lower(
            *place((s, pend["g"], pend["ut"], pend["v"], pend["step"],
                    p, mask)), k=k)
    return lowered.compile()


def _bytes(compiled):
    """A compiled program's argument, output and temporary bytes."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", [
    "f32-entry", "f32-fresh", "f32-deflated", "f32-fresh_refresh",
    "f32-deflated_refresh", "f64-entry", "f64-deflated",
    "f64-fresh_refresh", "f64-deflated_refresh"])
def test_128_programs_compile_for_v5e_at_64_chains(program, one_chip,
                                                   monkeypatch):
    """darcy-128-f32k's programs at 128² and 64 chains compile for one v5e
    chip and fit its memory: the fp32 passes' programs on the kernels, and
    the fp64 programs of the refinement driver's fallback passes (a kernel
    solver's fp64 cycles run on jnp). The Arnoldi sweep writes its new
    basis row by a select: a write at each chain's own step is a scatter
    over chains, which the fp64 cycles cannot fit in the 16 MiB of scoped
    VMEM at this size. (The fp64 fresh cycle compiles too, but needs about
    19.7 GB at 64 chains: PERF.md, open questions.) In the cycles, the
    fused kernel's operands sit where the benchmark's cost model
    (bench/cost.py) counts them: the basis and the recycle rows in HBM,
    the rest in VMEM."""
    dtype, name = program.split("-")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = dtype == "f32"
    compiled = _compiled(name, 64, one_chip, nx=128,
                         dt=jnp.float32 if f32 else jnp.float64,
                         use_kernel=True)
    assert 0 < _bytes(compiled) < 16e9
    if f32 and name in ("fresh", "deflated"):
        from bench import cost

        k = 15 if name == "deflated" else 0
        want = [where for _, _, _, where
                in cost.blocks(41 - k, k, 128, 128, cost.row_block(128))]
        got = _kernel_operands_in_hbm(compiled.as_text())
        assert got == want[:len(got)] and len(got) == 10, got


def _kernel_operands_in_hbm(hlo):
    """Per operand of the fused arnoldi_step call in compiled HLO text:
    True where its layout lacks the VMEM memory space S(1)."""
    import re

    call = re.search(r"%arnoldi_step[.\d]* = .*? custom-call\((.*?)\), "
                     r"custom_call_target", hlo)
    out = []
    for operand in call.group(1).split(","):
        name = operand.split("*/")[-1].strip()
        shape = re.search(re.escape(name) + r" = (\S+) ", hlo).group(1)
        out.append("S(1)" not in shape)
    return out


@pytest.mark.parametrize("name", ["fresh", "deflated"])
def test_fp64_host_eig_cycles_drop_the_sweep_loop(name):
    """With the eigensolve on the host, a cycle program holds no loop of
    its own: its loops are those of its Arnoldi sweep alone (an iterative
    eigensolve, like the subspace iteration's sweep `fori_loop`, adds
    one)."""
    from functools import partial

    from repro.solvers.arnoldi import _arnoldi_cycle_impl

    bsz = 8
    ops, s, aux = _cycle_shapes(bsz)
    fn, kw = _cycle_fn(name), _cycle_kw(name)

    def loops(f, *args):
        text = str(jax.make_jaxpr(f)(*args))  # a fori_loop may be a scan
        return text.count("while[") + text.count("scan[")

    width = kw["m"] if name == "fresh" else kw["mi"]
    c_rows = (jax.ShapeDtypeStruct((bsz, 0, s["r"].shape[1]), jnp.float64)
              if name == "fresh" else
              jax.ShapeDtypeStruct((bsz, kw["k"], s["r"].shape[1]),
                                   jnp.float64))
    sweep = jax.vmap(partial(_arnoldi_cycle_impl, m=width, orthog="cgs2",
                             use_kernel=False, h_acc="native"))
    arnoldi = loops(sweep, ops, c_rows, s["r"], aux["tol_abs"])
    assert arnoldi >= 1
    assert loops(lambda *a: fn.__wrapped__(*a, **kw), ops, s, aux) == arnoldi


def test_fp64_applies_leave_the_kernels_on_tpu(monkeypatch):
    """The fp64 work of a kernel solver (the mixed-precision residual
    replay, the fp64 fallback and retry cycles) applies through jnp where
    the kernels take no fp64, instead of raising; fp32 keeps the kernels."""
    from repro.kernels import ref
    from repro.pde.dia import DIA
    from repro.solvers.arnoldi import _arnoldi_cycle_impl
    from repro.solvers.operator import DIAOp, PreconditionedOp, StencilOp
    from repro.solvers.precond import JacobiPrecond

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.kernels_take(F32) and not ops.kernels_take(jnp.float64)
    c = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 4), jnp.float64)
    v = jnp.arange(16.0, dtype=jnp.float64)
    st = StencilOp(c, use_kernel=True)
    assert jnp.allclose(st.apply(v),
                        ref.stencil5_matvec(c, v.reshape(4, 4)).ravel())
    dia = DIA(offsets=(-4, -1, 0, 1, 4), data=c.reshape(5, 16))
    assert jnp.allclose(DIAOp(dia, use_kernel=True).apply(v),
                        ref.dia_spmv(dia.offsets, dia.data, v))
    op = PreconditionedOp(st, JacobiPrecond(jnp.ones(16)))
    cyc = _arnoldi_cycle_impl(op, jnp.zeros((0, 16)), v, 1e-12, m=4,
                              use_kernel=True)
    assert int(cyc.j_used) == 4 and jnp.isfinite(cyc.h).all()


def test_gspmd_partitioner_switch_exists():
    """`ChainSharding.partitioner()` uses JAX's private Shardy switch
    (`jax._src.config.use_shardy_partitioner`): this fails on the JAX that
    drops it, before a sharded solve on a TPU would."""
    from repro.distributed.sharding import ChainSharding

    was = jax.config.jax_use_shardy_partitioner
    with ChainSharding.partitioner():
        assert jax.config.jax_use_shardy_partitioner is False
    assert jax.config.jax_use_shardy_partitioner == was


@pytest.mark.parametrize("from_env", [False, True], ids=["checkout", "env"])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and the program sets no directory;
    otherwise the cache sits at the fixed `<root>/.jax_cache`."""
    from repro import compile_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env_dir = str(tmp_path / "from_env")
    if from_env:
        monkeypatch.setenv(compile_cache.ENV, env_dir)
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        got = compile_cache.enable(str(tmp_path))
        if from_env:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        else:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
