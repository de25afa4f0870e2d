"""Preconditioned solver tier: the M= seam, the three preconditioners,
and their composition with guards, blocks, and segmented checkpoints.

Acceptance pins of the preconditioning PR:

- PCG/PCGLS with M reach the SAME fixed point as the unpreconditioned
  solve in FEWER iterations (engine × precision sweep);
- ``M=None`` lowers to bit-identical HLO — the seam is free when off;
- the preconditioner apply fuses into the solver loop (zero host
  callbacks under guards);
- block (N,K) PCG preconditions all K columns in one apply and keeps
  per-column freeze/breakdown isolation under guards;
- segmented PCG banks the preconditioner signature in the checkpoint
  meta and REFUSES to resume under a different M.
"""


import numpy as np
import pytest
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
from pylops_mpi_tpu.linearoperator import MPILinearOperator
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.ops import _precision as PR
from pylops_mpi_tpu.ops.precond import (JacobiPrecond, BlockJacobiPrecond,
                                        VCyclePrecond, make_precond,
                                        probe_diagonal, _wrap_like)
from pylops_mpi_tpu.resilience import status as rstatus
from pylops_mpi_tpu.solvers import block_cg, block_cgls
from pylops_mpi_tpu.solvers.basic import (_cg_fused, _cgls_fused,
                                          cg_guarded)
from pylops_mpi_tpu.solvers.segmented import cg_segmented
from pylops_mpi_tpu.utils import hlo


@pytest.fixture(autouse=True)
def _fresh():
    PR.set_precision(None)
    rstatus.clear_statuses()
    yield
    PR.set_precision(None)
    rstatus.clear_statuses()


_strip = hlo.strip_provenance


def _varied_spd(rng, nblk=8, n=8, spread=1e2, dtype=np.float32):
    """Block-diag SPD with per-block scales spanning ``spread`` — the
    ill-conditioning is DIAGONAL, so Jacobi/block-Jacobi bite hard."""
    mats, scales = [], np.logspace(0, np.log10(spread), nblk)
    for s in scales:
        a = rng.standard_normal((n, n))
        mats.append(((a @ a.T) * 0.1 + n * np.eye(n)) * s)
    return mats


def _problem(rng, dtype=np.float32, nblk=8, n=8):
    mats = [m.astype(dtype) for m in _varied_spd(rng, nblk, n)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    import scipy.linalg as spla
    dense = spla.block_diag(*mats).astype(np.float64)
    xt = rng.standard_normal(nblk * n)
    y = DistributedArray.to_dist((dense @ xt).astype(dtype))
    return Op, dense, xt, y


def _lap_factory(dims):
    """SPD 5-point Dirichlet Laplacian on ``dims`` — the V-cycle's
    re-discretization hook (symmetric at the boundary, unlike the
    one-sided stencils of MPILaplacian)."""
    ny, nx = dims

    class Lap(MPILinearOperator):
        accepts_block = True

        def __init__(self):
            super().__init__(shape=(ny * nx, ny * nx),
                             dtype=np.float64)

        def _matvec(self, x):
            g = x._global()
            vec = g.ndim == 1
            t = g.reshape((ny, nx) if vec else (ny, nx, g.shape[-1]))
            p = jnp.pad(t, ((1, 1), (1, 1))
                        + (() if vec else ((0, 0),)))
            out = (4.0 * t - p[:-2, 1:-1] - p[2:, 1:-1]
                   - p[1:-1, :-2] - p[1:-1, 2:])
            return _wrap_like(out.reshape(g.shape), x)

        _rmatvec = _matvec

    return Lap()


# ------------------------------------------------------ diagonal probing
def test_blockdiag_diagonal_fast_path(rng):
    mats = _varied_spd(rng)
    Op = MPIBlockDiag([MatrixMult(m.astype(np.float32)) for m in mats])
    import scipy.linalg as spla
    want = np.diag(spla.block_diag(*mats)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(Op.diagonal()), want,
                               rtol=1e-6)
    # probe_diagonal resolves the method, no probing matvecs
    np.testing.assert_allclose(np.asarray(probe_diagonal(Op)), want,
                               rtol=1e-6)


def test_probe_diagonal_basis_fallback_exact(rng):
    A = rng.standard_normal((6, 6))
    Op = MPIBlockDiag([MatrixMult(A.astype(np.float64))])
    Op.diagonal = None  # shadow the method: forces the basis-probe path
    d = np.asarray(probe_diagonal(Op, nmax=16))
    np.testing.assert_allclose(d, np.diag(A), atol=1e-12)


def test_probe_diagonal_refuses_above_nmax(rng):
    Op = MPIBlockDiag([MatrixMult(
        rng.standard_normal((8, 8)).astype(np.float32))])
    Op.diagonal = None
    with pytest.raises(ValueError, match="nmax"):
        probe_diagonal(Op, nmax=4)


# --------------------------------------- oracle: same fixed point, fewer
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("engine", ["cg", "cgls"])
def test_pcg_same_fixed_point_fewer_iters(rng, engine, precision):
    """Jacobi-family PCG/PCGLS against the unpreconditioned engine at
    every storage precision: the preconditioned solve stops in
    STRICTLY fewer iterations and lands at least as close to the f64
    oracle."""
    PR.set_precision(precision)
    pmt.clear_fused_cache()
    Op, dense, xt, y = _problem(rng)
    oracle = np.linalg.solve(dense, dense @ xt)
    niter = 400
    rtol = 1e-4 if precision == "f32" else 3e-2
    # the fused stop test is ABSOLUTE on kold ≈ ||residual||²: scale
    # by the problem's own starting residual norm
    if engine == "cg":
        tol = float((rtol * np.linalg.norm(dense @ xt)) ** 2)
        M = JacobiPrecond.from_operator(Op)
        x0n, it0, _ = pmt.cg(Op, y, niter=niter, tol=tol)
        x1n, it1, _ = pmt.cg(Op, y, niter=niter, tol=tol, M=M)
    else:
        tol = float((rtol * np.linalg.norm(
            dense.T @ (dense @ xt))) ** 2)
        M = BlockJacobiPrecond.from_block_diag(Op, normal=True)
        r0 = pmt.cgls(Op, y, niter=niter, tol=tol)
        r1 = pmt.cgls(Op, y, niter=niter, tol=tol, M=M)
        x0n, it0, x1n, it1 = r0[0], r0[2], r1[0], r1[2]
    assert it1 < it0, (it1, it0)
    assert it0 < niter, it0  # the baseline really converged

    def rel(x):
        x = np.asarray(x.asarray(), dtype=np.float64)
        return np.linalg.norm(x - oracle) / np.linalg.norm(oracle)

    # both at engine precision; the preconditioned one no worse
    assert rel(x1n) <= max(rel(x0n) * 2.0,
                           1e-4 if precision == "f32" else 5e-2)


def test_vcycle_pcg_reduces_iterations(rng):
    """Geometric multigrid V-cycle on the Dirichlet Laplacian: ≥2×
    fewer PCG iterations, same solution."""
    dims = (16, 16)
    Op = _lap_factory(dims)
    M = VCyclePrecond(_lap_factory, dims, levels=2)
    y = DistributedArray.to_dist(
        rng.standard_normal(dims[0] * dims[1]))
    x0n, it0, _ = pmt.cg(Op, y, niter=400, tol=1e-8)
    x1n, it1, _ = pmt.cg(Op, y, niter=400, tol=1e-8, M=M)
    assert it1 * 2 <= it0, (it1, it0)
    np.testing.assert_allclose(np.asarray(x1n.asarray()),
                               np.asarray(x0n.asarray()), atol=1e-4)


def test_m_requires_fused_path(rng):
    Op, dense, xt, y = _problem(rng)
    M = JacobiPrecond.from_operator(Op)
    with pytest.raises(ValueError, match="fused"):
        pmt.cg(Op, y, niter=5, M=M, show=True)


# ------------------------------------------------------------- HLO pins
def test_m_none_hlo_bit_identity(rng):
    """The seam is free when off: an explicit ``M=None`` call and the
    default call lower to byte-identical optimized HLO, for CG and
    CGLS alike."""
    Op, dense, xt, y = _problem(rng)
    x0 = DistributedArray.to_dist(np.zeros(Op.shape[1],
                                           dtype=np.float32))

    def cg_default(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)

    def cg_none(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10, M=None)

    a = hlo.compiled_hlo(cg_default, y, x0, 0.0)
    b = hlo.compiled_hlo(cg_none, y, x0, 0.0)
    assert _strip(a) == _strip(b)

    def ls_default(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=10)

    def ls_none(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=10, M=None)

    a = hlo.compiled_hlo(ls_default, y, x0, 0.0, 0.0)
    b = hlo.compiled_hlo(ls_none, y, x0, 0.0, 0.0)
    assert _strip(a) == _strip(b)


def test_pcg_fuses_zero_host_callbacks(rng):
    """The preconditioner apply traces INTO the fused loop: a Jacobi
    PCG program contains no host callbacks, and differs from the
    unpreconditioned program (M really is in the loop)."""
    Op, dense, xt, y = _problem(rng)
    x0 = DistributedArray.to_dist(np.zeros(Op.shape[1],
                                           dtype=np.float32))
    M = JacobiPrecond.from_operator(Op)

    def f(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10, M=M)

    h = hlo.assert_no_host_callbacks(f, y, x0, 0.0)

    def f0(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)

    assert _strip(h) != _strip(hlo.compiled_hlo(f0, y, x0, 0.0))


# ------------------------------------------------- block (N, K) PCG
def test_block_pcg_matches_single_rhs_oracle(rng):
    """One M apply preconditions all K columns; every column equals
    its own single-RHS PCG solve."""
    K, dtype = 3, np.float32
    mats = [m.astype(dtype) for m in _varied_spd(rng)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    M = JacobiPrecond.from_operator(Op)
    N = Op.shape[0]
    Y = rng.standard_normal((N, K)).astype(dtype)
    yb = DistributedArray(global_shape=(N, K), dtype=dtype)
    yb[:] = Y
    xb, _, _ = block_cg(Op, yb, niter=60, tol=0.0, M=M)
    for j in range(K):
        yj = DistributedArray.to_dist(np.ascontiguousarray(Y[:, j]))
        xj, _, _ = pmt.cg(Op, yj, niter=60, tol=0.0, M=M)
        np.testing.assert_allclose(np.asarray(xb.array)[:, j],
                                   np.asarray(xj.array),
                                   rtol=0, atol=1e-4)


def test_block_pcg_poisoned_column_freezes_alone(rng):
    """GUARDS=on block PCG: a NaN column breaks down alone; clean
    columns match the clean preconditioned block solve."""
    K, dtype = 4, np.float32
    mats = [m.astype(dtype) for m in _varied_spd(rng)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    M = JacobiPrecond.from_operator(Op)
    N = Op.shape[0]
    Y = rng.standard_normal((N, K)).astype(dtype)
    yb = DistributedArray(global_shape=(N, K), dtype=dtype)
    yb[:] = Y
    x_clean, _, _ = block_cg(Op, yb, niter=80, tol=1e-6, M=M)
    Yp = Y.copy()
    Yp[0, 1] = np.nan
    yp = DistributedArray(global_shape=(N, K), dtype=dtype)
    yp[:] = Yp
    xp, _, _ = block_cg(Op, yp, niter=80, tol=1e-6, guards=True, M=M)
    info = rstatus.last_status("block_cg")
    assert info["columns"][1] == rstatus.BREAKDOWN
    for j in (0, 2, 3):
        assert info["columns"][j] == rstatus.CONVERGED
        np.testing.assert_allclose(np.asarray(xp.array)[:, j],
                                   np.asarray(x_clean.array)[:, j],
                                   rtol=0, atol=1e-5)


def test_block_pcgls_fixed_point(rng):
    """Preconditioned block CGLS (normal-equation block-Jacobi M)
    reaches the least-squares fixed point of every column."""
    K, dtype = 2, np.float32
    mats = [rng.standard_normal((10, 6)).astype(dtype)
            for _ in range(8)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    M = BlockJacobiPrecond.from_block_diag(Op, normal=True)
    N = Op.shape[0]
    Y = rng.standard_normal((N, K)).astype(dtype)
    yb = DistributedArray(global_shape=(N, K), dtype=dtype)
    yb[:] = Y
    xb = block_cgls(Op, yb, niter=40, tol=0.0, M=M)[0]
    import scipy.linalg as spla
    dense = spla.block_diag(*mats).astype(np.float64)
    want = np.linalg.lstsq(dense, Y.astype(np.float64), rcond=None)[0]
    np.testing.assert_allclose(np.asarray(xb.array), want, atol=2e-3)


# ------------------------------------------------- segmented PCG resume
def test_segmented_pcg_kill_resume_and_m_mismatch(rng, tmp_path):
    """Segmented PCG kill/resume reproduces the uninterrupted
    trajectory bit-for-bit; a resume under a DIFFERENT preconditioner
    refuses (the checkpoint meta banks M's signature)."""
    Op, dense, xt, y = _problem(rng)
    M = JacobiPrecond.from_operator(Op)
    ref = cg_segmented(Op, y, niter=20, tol=0.0, epoch=5, M=M)
    path = str(tmp_path / "pcg.ckpt")

    class Kill(Exception):
        pass

    def killer(info):
        if info["epoch"] == 2:
            raise Kill

    with pytest.raises(Kill):
        cg_segmented(Op, y, niter=20, tol=0.0, epoch=5, M=M,
                     checkpoint_path=path, on_epoch=killer)
    res = cg_segmented(Op, y, niter=20, tol=0.0, epoch=5, M=M,
                       checkpoint_path=path)
    assert res.iiter == ref.iiter
    np.testing.assert_array_equal(np.asarray(res.x.array),
                                  np.asarray(ref.x.array))
    np.testing.assert_array_equal(res.cost, ref.cost)

    # fresh checkpoint banked under M, resumed without it → refuse
    path2 = str(tmp_path / "pcg2.ckpt")
    cg_segmented(Op, y, niter=10, tol=0.0, epoch=5, M=M,
                 checkpoint_path=path2)
    with pytest.raises(ValueError, match="resume must replay"):
        cg_segmented(Op, y, niter=10, tol=0.0, epoch=5,
                     checkpoint_path=path2)


# ------------------------------------------------------- knob dispatch
def test_make_precond_knob_dispatch(rng, monkeypatch):
    Op, dense, xt, y = _problem(rng)
    assert make_precond(Op, kind="none") is None
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECOND", "jacobi")
    M = make_precond(Op)
    assert isinstance(M, JacobiPrecond)
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECOND", "block_jacobi")
    M = make_precond(Op)
    assert isinstance(M, BlockJacobiPrecond)
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECOND", "mg")
    with pytest.raises(ValueError, match="op_factory"):
        make_precond(Op)
    M = make_precond(Op, kind="mg", op_factory=_lap_factory,
                     dims=(8, 8), levels=2)
    assert isinstance(M, VCyclePrecond)
    with pytest.raises(ValueError, match="kind"):
        make_precond(Op, kind="nope")


def test_mg_levels_knob(monkeypatch):
    from pylops_mpi_tpu.utils.deps import mg_levels_default
    monkeypatch.setenv("PYLOPS_MPI_TPU_MG_LEVELS", "5")
    assert mg_levels_default() == 5
    monkeypatch.setenv("PYLOPS_MPI_TPU_MG_LEVELS", "junk")
    assert mg_levels_default() == 3
    monkeypatch.setenv("PYLOPS_MPI_TPU_MG_LEVELS", "0")
    assert mg_levels_default() == 1


# ------------------------------------------------------- serving seam
def test_family_spec_with_preconditioner(rng):
    """A FamilySpec carrying M serves preconditioned packed solves —
    and converges where the bare family at the same niter cannot."""
    from pylops_mpi_tpu.serving.engine import FamilySpec, WarmPool
    mats = [m.astype(np.float32) for m in _varied_spd(rng)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    M = JacobiPrecond.from_operator(Op)
    pool = WarmPool(buckets=(4,))
    pool.register(FamilySpec(name="prec", operator=Op, solver="cg",
                             niter=40, tol=1e-6, M=M))
    pool.register(FamilySpec(name="bare", operator=Op, solver="cg",
                             niter=40, tol=1e-6))
    Y = rng.standard_normal((Op.shape[0], 3)).astype(np.float32)
    outp = pool.solve("prec", Y)
    outb = pool.solve("bare", Y)
    assert set(outp.statuses) == {"converged"}
    assert outp.iiter <= outb.iiter
