"""Distributed sparse MatrixMult tier: dense-oracle parity on ragged
row shards, ring-vs-scatter adjoint parity, cost model ∝ nnz, the
tuner's sparse-vs-dense tier pick, and the tier-off HLO pin.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from pylops_mpi_tpu import DistributedArray
from pylops_mpi_tpu.diagnostics import costmodel
from pylops_mpi_tpu.linearoperator import operator_is_jit_arg
from pylops_mpi_tpu.ops.matrixmult import MPIMatrixMult
from pylops_mpi_tpu.ops.sparse import (MPISparseMatrixMult,
                                       auto_sparse_matmult)
from pylops_mpi_tpu.utils import hlo


def _sparse_problem(rng, N=37, M=53, density=0.08, cmplx=False):
    """N=37 splits ragged on every CI device count (2, 4, 8)."""
    A = rng.standard_normal((N, M)) * (rng.random((N, M)) < density)
    if cmplx:
        A = A + 1j * rng.standard_normal((N, M)) * (A != 0)
    return A


@pytest.mark.parametrize("cmplx", [False, True])
def test_matches_dense_oracle_ragged(rng, cmplx):
    A = _sparse_problem(rng, cmplx=cmplx)
    N, M = A.shape
    Sp = MPISparseMatrixMult.from_dense(A)
    assert 0 < Sp.nnz < N * M
    sizes = {s[0] for s in DistributedArray.to_dist(
        np.zeros(N)).local_shapes}
    assert len(sizes) > 1  # genuinely ragged row shards
    x = rng.standard_normal(M) + (1j * rng.standard_normal(M)
                                  if cmplx else 0)
    y = rng.standard_normal(N) + (1j * rng.standard_normal(N)
                                  if cmplx else 0)
    f = np.asarray(Sp.matvec(DistributedArray.to_dist(x)).asarray())
    a = np.asarray(Sp.rmatvec(DistributedArray.to_dist(y)).asarray())
    np.testing.assert_allclose(f, A @ x, atol=1e-6)
    np.testing.assert_allclose(a, A.conj().T @ y, atol=1e-6)


def test_block_rhs_and_jit_arg(rng):
    A = _sparse_problem(rng)
    N, M = A.shape
    Sp = MPISparseMatrixMult.from_dense(A)
    assert Sp.accepts_block and operator_is_jit_arg(Sp)
    K = 3
    X = rng.standard_normal((M, K))
    Y = rng.standard_normal((N, K))
    fB = np.asarray(Sp.matvec(DistributedArray.to_dist(X)).asarray())
    aB = np.asarray(Sp.rmatvec(DistributedArray.to_dist(Y)).asarray())
    np.testing.assert_allclose(fB, A @ X, atol=1e-6)
    np.testing.assert_allclose(aB, A.T @ Y, atol=1e-6)


def test_ring_adjoint_matches_scatter(rng):
    A = _sparse_problem(rng)
    N, M = A.shape
    y = rng.standard_normal(N)
    dy = DistributedArray.to_dist(y)
    sc = MPISparseMatrixMult.from_dense(A)
    rg = MPISparseMatrixMult.from_dense(A, adjoint_mode="ring")
    a_sc = np.asarray(sc.rmatvec(dy).asarray())
    a_rg = np.asarray(rg.rmatvec(dy).asarray())
    np.testing.assert_allclose(a_rg, a_sc, atol=1e-6)
    np.testing.assert_allclose(a_rg, A.T @ y, atol=1e-6)


def test_ring_adjoint_schedule_shape():
    """The ring path really is a ring: P-1 ppermutes, no all-to-all of
    the triplets."""
    import numpy as _np
    rng = _np.random.default_rng(0)
    A = _sparse_problem(rng, N=64, M=64, density=0.1)
    rg = MPISparseMatrixMult.from_dense(A, adjoint_mode="ring")
    prod = jnp.asarray(rng.standard_normal(rg.nnz))
    h = hlo.compiled_hlo(rg._rmatvec_ring, prod)
    P = jax.device_count()
    # two leaves (vals, cols) rotate through P-1 ring steps
    assert hlo.count_ops(h, "collective-permute") == 2 * (P - 1)
    assert hlo.count_ops(h, "all-to-all") == 0


def test_unsorted_triplets_are_sorted(rng):
    A = _sparse_problem(rng, N=12, M=12, density=0.3)
    rows, cols = np.nonzero(A)
    perm = rng.permutation(len(rows))
    Sp = MPISparseMatrixMult(rows[perm], cols[perm],
                             A[rows, cols][perm], A.shape)
    x = rng.standard_normal(12)
    f = np.asarray(Sp.matvec(DistributedArray.to_dist(x)).asarray())
    np.testing.assert_allclose(f, A @ x, atol=1e-6)


def test_diagonal_banded_todense(rng):
    A = _sparse_problem(rng, N=16, M=16, density=0.3)
    np.fill_diagonal(A, np.arange(1, 17))
    Sp = MPISparseMatrixMult.from_dense(A)
    np.testing.assert_allclose(np.asarray(Sp.diagonal()),
                               np.diag(A), atol=1e-6)
    np.testing.assert_allclose(np.asarray(Sp.todense()), A, atol=1e-6)
    bands = [np.arange(1, 10, dtype=float),
             np.arange(10, 20, dtype=float),
             np.arange(2, 11, dtype=float)]
    Sb = MPISparseMatrixMult.from_banded([-1, 0, 1], bands, (10, 10))
    ref = (np.diag(bands[1]) + np.diag(bands[0], -1)
           + np.diag(bands[2], 1))
    np.testing.assert_allclose(np.asarray(Sb.todense()), ref)
    with pytest.raises(ValueError, match="outside shape"):
        MPISparseMatrixMult([11], [0], [1.0], (10, 10))


def test_solver_integration_cgls(rng):
    """The sparse operator drives the fused CGLS loop end to end."""
    import pylops_mpi_tpu as pmt
    A = _sparse_problem(rng, N=48, M=24, density=0.3)
    A += np.pad(np.eye(24), ((0, 24), (0, 0)))  # full column rank
    Sp = MPISparseMatrixMult.from_dense(A)
    xt = rng.standard_normal(24)
    y = DistributedArray.to_dist(A @ xt)
    x = pmt.cgls(Sp, y, niter=120, tol=0.0)[0]
    want = np.linalg.lstsq(A, A @ xt, rcond=None)[0]
    np.testing.assert_allclose(np.asarray(x.asarray()), want,
                               atol=1e-3)


# ------------------------------------------------------- cost + tuner
def test_cost_model_scales_with_nnz(rng):
    A = _sparse_problem(rng, N=64, M=64, density=0.05)
    Sp = MPISparseMatrixMult.from_dense(A)
    c = costmodel.estimate(Sp, "forward")
    P = jax.device_count()
    assert c.flops == pytest.approx(2.0 * Sp.nnz / P)
    A2 = _sparse_problem(rng, N=64, M=64, density=0.30)
    Sp2 = MPISparseMatrixMult.from_dense(A2)
    c2 = costmodel.estimate(Sp2, "forward")
    assert c2.flops > 3 * c.flops
    ca = costmodel.estimate(Sp, "adjoint")
    assert ca.ici_bytes > 0  # the scatter combine is charged


def test_tuner_picks_sparse_at_high_sparsity(rng, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    A = _sparse_problem(rng, N=64, M=64, density=0.10)  # 90% sparse
    op = auto_sparse_matmult(A)
    assert isinstance(op, MPISparseMatrixMult)
    Ad = rng.standard_normal((64, 64))
    assert not isinstance(auto_sparse_matmult(Ad),
                          MPISparseMatrixMult)


def test_tier_off_hlo_bit_identical(rng, monkeypatch):
    """Tuning off (the default): ``auto_sparse_matmult`` lowers to the
    exact dense program a direct MPIMatrixMult construction lowers to
    — the sparse tier is invisible until asked for."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE", raising=False)
    A = _sparse_problem(rng, N=32, M=32, density=0.05)
    auto = auto_sparse_matmult(A)
    direct = MPIMatrixMult(A, 1)
    assert type(auto) is type(direct)
    x = DistributedArray.to_dist(np.zeros(32))

    ha = hlo.compiled_hlo(lambda v: auto.matvec(v).array, x)
    hd = hlo.compiled_hlo(lambda v: direct.matvec(v).array, x)
    assert hlo.strip_provenance(ha) == hlo.strip_provenance(hd)
