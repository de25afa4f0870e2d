"""Application pipeline tests (poststack, mdd) — the reference validates
these via tutorial smoke runs under mpiexec; here they are real tests."""

import numpy as np
import pytest

from pylops_mpi_tpu.models import (PoststackLinearModelling,
                                   MPIPoststackLinearModelling,
                                   poststack_inversion, ricker, mdd,
                                   kernel_to_frequency)
from pylops_mpi_tpu import DistributedArray, Partition
import jax.numpy as jnp


def test_ricker():
    w, t = ricker(np.arange(0, 0.04, 0.004), f0=20)
    assert w.shape == t.shape
    assert np.argmax(w) == len(w) // 2


def test_poststack_forward_oracle(rng):
    """Local modelling equals explicit 0.5*conv(deriv) computation."""
    nt0 = 32
    wav, _ = ricker(np.arange(0, 0.02, 0.002), f0=30)
    op = PoststackLinearModelling(wav, nt0, dtype=np.float64)
    m = rng.standard_normal(nt0)
    got = np.asarray(op.matvec(jnp.asarray(m)))
    dm = np.zeros(nt0)
    dm[1:-1] = 0.5 * (m[2:] - m[:-2])
    dm[0] = m[1] - m[0]
    dm[-1] = m[-1] - m[-2]
    full = np.convolve(dm, wav)
    expected = 0.5 * full[len(wav) // 2: len(wav) // 2 + nt0]
    np.testing.assert_allclose(got, expected, rtol=1e-10)


# each cell compiles a full solver program (~11 s); the matmul-fft CI
# leg runs this file unfiltered, so both rows ride -m slow since the
# ISSUE 13 wall-budget audit
@pytest.mark.parametrize("epsR", [
    pytest.param(None, marks=pytest.mark.slow),
    pytest.param(0.01, marks=pytest.mark.slow)])
def test_poststack_inversion(rng, epsR):
    nx, nt0 = 16, 64
    wav, _ = ricker(np.arange(0, 0.02, 0.002), f0=25)
    # smooth impedance model
    m = np.cumsum(rng.standard_normal((nx, nt0)) * 0.05, axis=1)
    Op = MPIPoststackLinearModelling(wav, nt0, nx)
    dm = DistributedArray.to_dist(m.ravel(), local_shapes=Op.local_shapes_m)
    d = Op.matvec(dm).asarray().reshape(nx, nt0)
    minv, _ = poststack_inversion(d, wav, niter=150, epsR=epsR,
                                  damp=1e-3)
    # modelling operator has a null space (constant per trace); compare
    # through the forward operator instead of the model directly
    dminv = DistributedArray.to_dist(minv.ravel(),
                                     local_shapes=Op.local_shapes_m)
    dre = Op.matvec(dminv).asarray().reshape(nx, nt0)
    assert np.linalg.norm(dre - d) / np.linalg.norm(d) < 5e-2


def test_mdd_roundtrip(rng):
    """mdd() recovers the model that generated the data."""
    ns, nr, nt, nv = 4, 3, 17, 1
    Gt = rng.standard_normal((ns, nr, nt)) * np.exp(
        -0.3 * np.arange(nt))[None, None, :]
    G = kernel_to_frequency(Gt)
    from pylops_mpi_tpu import MPIMDC
    from pylops_mpi_tpu.distributedarray import Partition
    Op = MPIMDC(G, nt=nt, nv=nv, twosided=True)
    xtrue = rng.standard_normal(nt * nr * nv)
    d = Op.matvec(DistributedArray.to_dist(
        xtrue, partition=Partition.BROADCAST)).asarray().reshape(nt, ns, nv)
    minv, _ = mdd(G, d, nt=nt, nv=nv, niter=300)
    np.testing.assert_allclose(minv.ravel(), xtrue, rtol=1e-3, atol=1e-5)


# --------------------------------------------------------------- LSM
def _lsm_geometry():
    nx, nz = 21, 16
    dx = 4.0
    x, z = np.arange(nx) * dx, np.arange(nz) * dx
    nr, ns = 5, 8            # a source a device of the test mesh
    recs = np.vstack((np.linspace(2 * dx, (nx - 2) * dx, nr),
                      8 * np.ones(nr)))
    srcs = np.vstack((np.linspace(2 * dx, (nx - 2) * dx, ns),
                      4 * np.ones(ns)))
    nt = 160
    t = np.arange(nt) * 0.002
    wav, _ = ricker(t[:11], f0=25)
    return z, x, t, srcs, recs, wav, len(wav) // 2


def test_kirchhoff_dottest(rng):
    from pylops_mpi_tpu.models import KirchhoffDemigration
    z, x, t, srcs, recs, wav, wavc = _lsm_geometry()
    Kop = KirchhoffDemigration(z, x, t, srcs, recs, 1000.0, wav, wavc,
                               dtype=np.float64)
    u = rng.standard_normal(Kop.shape[1])
    v = rng.standard_normal(Kop.shape[0])
    lhs = np.asarray(Kop.matvec(jnp.asarray(u))) @ v
    rhs = u @ np.asarray(Kop.rmatvec(jnp.asarray(v)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-10)


def test_spray_oracle(rng):
    """TravelTimeSpray against an explicit dense scatter oracle."""
    from pylops_mpi_tpu.models import TravelTimeSpray
    npairs, npix, nt = 3, 7, 12
    itrav = rng.integers(0, nt + 3, size=(npairs, npix))  # some invalid
    amp = rng.standard_normal((npairs, npix))
    op = TravelTimeSpray(itrav, amp, nt, dtype=np.float64)
    m = rng.standard_normal(npix)
    dense = np.zeros((npairs, nt))
    for p in range(npairs):
        for i in range(npix):
            if itrav[p, i] < nt:
                dense[p, itrav[p, i]] += amp[p, i] * m[i]
    np.testing.assert_allclose(
        np.asarray(op.matvec(jnp.asarray(m))).reshape(npairs, nt), dense,
        rtol=1e-12)


def test_lsm_inversion_reduces_cost():
    from pylops_mpi_tpu.models import lsm
    z, x, t, srcs, recs, wav, wavc = _lsm_geometry()
    refl = np.zeros((len(z), len(x)))
    refl[8] = 1.0
    minv, d, cost = lsm(z, x, t, srcs, recs, 1000.0, wav, wavc, refl,
                        niter=15, dtype=np.float64)
    assert minv.shape == refl.shape
    assert cost[-1] < 0.5 * cost[0]
    # the interface row should carry the most energy
    assert np.abs(minv).sum(axis=1).argmax() == 8


def test_poststack_wavelet_sweep(rng):
    """Poststack forward against the dense convolution-derivative chain
    for several wavelet lengths."""
    from pylops_mpi_tpu.models import ricker, MPIPoststackLinearModelling
    nt0, nx = 64, 16
    m = rng.standard_normal((nx, nt0))
    dm = DistributedArray.to_dist(m.ravel())
    for ntw in (15, 31):
        wav = ricker(np.arange(ntw) * 0.004, f0=20)[0]
        Op = MPIPoststackLinearModelling(wav, nt0, nx, dtype=np.float64)
        d = Op.matvec(dm).asarray()
        assert d.shape == (nx * nt0,)
        assert np.isfinite(d).all()
        # linearity in the model
        d2 = Op.matvec(DistributedArray.to_dist(2.0 * m.ravel())).asarray()
        np.testing.assert_allclose(d2, 2.0 * d, rtol=1e-10, atol=1e-10)


def test_mdc_adjoint_identity(rng):
    """MDC forward/adjoint satisfy the real-part adjoint identity (MDC
    is real-linear through the rFFT sandwich, ref MDC.py:55-74)."""
    from pylops_mpi_tpu import MPIMDC
    nt, nv, nr, ns = 16, 2, 4, 3
    nfmax = nt // 2 + 1
    G = (rng.standard_normal((nfmax, ns, nr))
         + 1j * rng.standard_normal((nfmax, ns, nr)))
    Op = MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=1.0, twosided=False)
    u = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(np.float32),
        partition=Partition.BROADCAST)
    v = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[0]).astype(np.float32),
        partition=Partition.BROADCAST)
    yv = np.vdot(Op.matvec(u).asarray(), v.asarray())
    ux = np.vdot(u.asarray(), Op.rmatvec(v).asarray())
    np.testing.assert_allclose(np.real(yv), np.real(ux), rtol=2e-4)
