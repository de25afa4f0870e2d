"""MPIFredholm1 + MPIMDC tests — mirrors the reference's
``tests/test_fredholm.py``: brute-force batched matmul oracle and MDC
chain consistency."""

import numpy as np
import pytest

from pylops_mpi_tpu import (DistributedArray, Partition, MPIFredholm1,
                            MPIMDC, cgls, dottest)


@pytest.mark.parametrize("nsl,nx,ny,nz", [(16, 5, 4, 1), (16, 5, 4, 3),
                                          (17, 4, 6, 2)])
@pytest.mark.parametrize("cmplx", [False, True])
def test_fredholm1(rng, nsl, nx, ny, nz, cmplx):
    G = rng.standard_normal((nsl, nx, ny))
    dt = np.float64
    if cmplx:
        G = G + 1j * rng.standard_normal((nsl, nx, ny))
        dt = np.complex128
    Op = MPIFredholm1(G, nz=nz, dtype=dt)
    m = rng.standard_normal((nsl, ny, nz)).astype(dt)
    d = rng.standard_normal((nsl, nx, nz)).astype(dt)
    dm = DistributedArray.to_dist(m.ravel(), partition=Partition.BROADCAST)
    dd = DistributedArray.to_dist(d.ravel(), partition=Partition.BROADCAST)
    got = Op.matvec(dm).asarray().reshape(nsl, nx, nz)
    expected = np.einsum("kxy,kyz->kxz", G, m)
    np.testing.assert_allclose(got, expected, rtol=1e-10)
    gotH = Op.rmatvec(dd).asarray().reshape(nsl, ny, nz)
    np.testing.assert_allclose(gotH,
                               np.einsum("kyx,kxz->kyz",
                                         G.conj().transpose(0, 2, 1), d),
                               rtol=1e-10)
    dottest(Op, dm, dd)


def test_fredholm1_saveGt(rng):
    G = rng.standard_normal((16, 4, 5))
    Op1 = MPIFredholm1(G, nz=2, saveGt=True, dtype=np.float64)
    Op2 = MPIFredholm1(G, nz=2, saveGt=False, dtype=np.float64)
    d = DistributedArray.to_dist(rng.standard_normal(16 * 4 * 2),
                                 partition=Partition.BROADCAST)
    np.testing.assert_allclose(Op1.rmatvec(d).asarray(),
                               Op2.rmatvec(d).asarray(), rtol=1e-12)


def test_fredholm1_few_slices_ok(rng):
    """The reference raises when a rank gets < 2 slices
    (ref Fredholm1.py:79-83); the batched-einsum rebuild has no such
    limit — fewer slices than devices must still work."""
    G = rng.standard_normal((3, 2, 2))
    Op = MPIFredholm1(G, nz=1, dtype=np.float64)
    m = rng.standard_normal(3 * 2)
    dm = DistributedArray.to_dist(m, partition=Partition.BROADCAST)
    got = Op.matvec(dm).asarray().reshape(3, 2)
    np.testing.assert_allclose(
        got, np.einsum("kxy,ky->kx", G, m.reshape(3, 2)), rtol=1e-12)


def _dense_mdc_oracle(G, nt, nv, dt, dr, twosided, x):
    """Serial MDC: F1ᴴ I1ᴴ Fr I F x with numpy (pylops conventions)."""
    nfmax, ns, nr = G.shape
    nfft = int(np.ceil((nt + 1) / 2))
    xt = x.reshape(nt, nr, nv)
    if twosided:
        xt = np.fft.ifftshift(xt, axes=0)
    X = np.fft.rfft(xt, n=nt, axis=0) / np.sqrt(nt)
    X[1:1 + (nt - 1) // 2] *= np.sqrt(2)
    X = X[:nfmax]
    Y = np.einsum("kxy,kyz->kxz", dr * dt * np.sqrt(nt) * G, X)
    Yf = np.zeros((nfft, ns, nv), dtype=Y.dtype)
    Yf[:nfmax] = Y
    Yf[1:1 + (nt - 1) // 2] /= np.sqrt(2)
    y = np.fft.irfft(Yf * np.sqrt(nt), n=nt, axis=0) / np.sqrt(nt) * np.sqrt(nt)
    return y.ravel()


def test_mdc_forward_matches_manual(rng):
    """MDC chain equals a step-by-step numpy computation."""
    nt, nr, ns, nv, nfmax = 17, 4, 5, 1, 9
    G = rng.standard_normal((nfmax, ns, nr)) + 1j * rng.standard_normal(
        (nfmax, ns, nr))
    Op = MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=2.0, twosided=True)
    assert Op.shape == (nt * ns * nv, nt * nr * nv)
    x = rng.standard_normal(nt * nr * nv)
    dx = DistributedArray.to_dist(x, partition=Partition.BROADCAST)
    got = Op.matvec(dx).asarray()
    # manual chain with the same local operators
    from pylops_mpi_tpu.ops.local import FFT, Identity
    import jax.numpy as jnp
    F = FFT((nt, nr, nv), axis=0, real=True, ifftshift_before=True,
            dtype=np.float64)
    F1 = FFT((nt, ns, nv), axis=0, real=True, dtype=np.float64)
    nfft = int(np.ceil((nt + 1) / 2))
    X = np.asarray(F.matvec(jnp.asarray(x))).reshape(nfft, nr, nv)[:nfmax]
    Y = np.einsum("kxy,kyz->kxz", 2.0 * 0.004 * np.sqrt(nt) * G, X)
    Yf = np.zeros((nfft, ns, nv), dtype=Y.dtype)
    Yf[:nfmax] = Y
    expected = np.asarray(F1.rmatvec(jnp.asarray(Yf.ravel())))
    np.testing.assert_allclose(got, expected, rtol=1e-9)


def test_mdc_even_nt_twosided_raises():
    with pytest.raises(ValueError):
        MPIMDC(np.ones((4, 3, 3), dtype=np.complex128), nt=16, nv=1)


def test_mdc_inversion(rng):
    """Small MDD-style inversion: recover model through MDC with CGLS
    (the tutorials/mdd.py pattern)."""
    nt, nr, ns, nv = 17, 3, 4, 1
    nfft = int(np.ceil((nt + 1) / 2))
    G = (rng.standard_normal((nfft, ns, nr))
         + 1j * rng.standard_normal((nfft, ns, nr)))
    Op = MPIMDC(G, nt=nt, nv=nv, dt=1.0, dr=1.0, twosided=True)
    xtrue = rng.standard_normal(nt * nr * nv)
    dy = Op.matvec(DistributedArray.to_dist(
        xtrue, partition=Partition.BROADCAST))
    x0 = DistributedArray.to_dist(np.zeros(nt * nr * nv),
                                  partition=Partition.BROADCAST)
    x, *_ = cgls(Op, dy, x0, niter=300, tol=1e-14)
    np.testing.assert_allclose(x.asarray(), xtrue, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("cmplx", [False, True])
@pytest.mark.parametrize("usematmul", [True, False])
def test_fredholm1_adjoint_oracle(rng, cmplx, usematmul):
    """Adjoint against the dense batched G^H y oracle + dottest
    (ref tests/test_fredholm.py dtype parametrization)."""
    nsl, nx, ny, nz = 8, 5, 4, 3
    dt = np.complex128 if cmplx else np.float64
    G = rng.standard_normal((nsl, nx, ny))
    if cmplx:
        G = G + 1j * rng.standard_normal((nsl, nx, ny))
    G = G.astype(dt)
    Fr = MPIFredholm1(G, nz=nz, dtype=dt)
    y = rng.standard_normal((nsl, nx, nz))
    if cmplx:
        y = y + 1j * rng.standard_normal((nsl, nx, nz))
    dy = DistributedArray.to_dist(y.ravel().astype(dt),
                                  partition=Partition.BROADCAST)
    got = Fr.rmatvec(dy).asarray().reshape(nsl, ny, nz)
    expected = np.einsum("sxy,sxz->syz", G.conj(), y)
    np.testing.assert_allclose(got, expected, rtol=1e-10, atol=1e-11)
    u = DistributedArray.to_dist(
        (rng.standard_normal(Fr.shape[1])
         + (1j * rng.standard_normal(Fr.shape[1]) if cmplx else 0)
         ).astype(dt), partition=Partition.BROADCAST)
    v = DistributedArray.to_dist(
        (rng.standard_normal(Fr.shape[0])
         + (1j * rng.standard_normal(Fr.shape[0]) if cmplx else 0)
         ).astype(dt), partition=Partition.BROADCAST)
    yv = np.vdot(Fr.matvec(u).asarray(), v.asarray())
    ux = np.vdot(u.asarray(), Fr.rmatvec(v).asarray())
    np.testing.assert_allclose(yv, ux, rtol=1e-10)


def test_fredholm1_cgls_inversion(rng):
    """Frequency-sharded least-squares inversion through Fredholm1
    (the MDD core problem, ref tutorials/mdd.py)."""
    nsl, nx, ny, nz = 8, 8, 4, 2
    G = rng.standard_normal((nsl, nx, ny))
    Fr = MPIFredholm1(G, nz=nz, dtype=np.float64)
    mtrue = rng.standard_normal((nsl, ny, nz))
    y = np.einsum("sxy,syz->sxz", G, mtrue)
    dy = DistributedArray.to_dist(y.ravel(),
                                  partition=Partition.BROADCAST)
    from pylops_mpi_tpu import cgls
    x0 = DistributedArray.to_dist(np.zeros(nsl * ny * nz),
                                  partition=Partition.BROADCAST)
    m, *_ = cgls(Fr, dy, x0, niter=300, tol=1e-14)
    np.testing.assert_allclose(m.asarray().reshape(nsl, ny, nz), mtrue,
                               rtol=1e-5, atol=1e-7)


def test_fredholm1_scatter_zero_comm(rng):
    """Beyond-reference path (SURVEY §7.10): SCATTER model/data aligned
    with G's frequency sharding — identical numbers to the BROADCAST
    path and a compiled program with ZERO collectives (each device
    contracts its own slice batch; 1/P the replicated-model memory)."""
    import jax
    from pylops_mpi_tpu import Partition
    from pylops_mpi_tpu.utils import collective_report
    # the zero-comm SCATTER path exists iff nsl %% n_devices == 0
    nsl, nx, ny, nz = 2 * len(jax.devices()), 6, 5, 3
    G = rng.standard_normal((nsl, nx, ny))
    Fr = MPIFredholm1(G, nz=nz, dtype=np.float64)
    m_np = rng.standard_normal(nsl * ny * nz)

    mb = DistributedArray.to_dist(m_np, partition=Partition.BROADCAST)
    ms = DistributedArray.to_dist(m_np,
                                  local_shapes=Fr.model_local_shapes)
    yb = Fr.matvec(mb)
    ys = Fr.matvec(ms)
    assert ys.partition == Partition.SCATTER
    np.testing.assert_allclose(np.asarray(ys.asarray()),
                               np.asarray(yb.asarray()), rtol=1e-13)

    d_np = rng.standard_normal(nsl * nx * nz)
    db = DistributedArray.to_dist(d_np, partition=Partition.BROADCAST)
    ds = DistributedArray.to_dist(d_np,
                                  local_shapes=Fr.data_local_shapes)
    np.testing.assert_allclose(np.asarray(Fr.rmatvec(ds).asarray()),
                               np.asarray(Fr.rmatvec(db).asarray()),
                               rtol=1e-13)

    # the whole sharded apply compiles to zero collectives
    rep = collective_report(lambda v: Fr.matvec(v).array, ms)
    assert rep == {}, rep
    rep_adj = collective_report(lambda v: Fr.rmatvec(v).array, ds)
    assert rep_adj == {}, rep_adj


def test_fredholm1_scatter_misaligned_raises(rng):
    """SCATTER vectors whose shards are not slice-aligned are rejected
    with guidance (silent wrong slicing would be worse)."""
    import jax
    P = len(jax.devices())
    G = rng.standard_normal((2 * P, 4, 3))
    Fr = MPIFredholm1(G, nz=1, dtype=np.float64)
    # a deliberately misaligned ragged split: off-by-one sizes on the
    # first/last shards break slice alignment at any device count
    n = Fr.shape[1]
    sizes = [n // P + (1 if i == 0 else 0) - (1 if i == P - 1 else 0)
             for i in range(P)]
    bad = DistributedArray.to_dist(rng.standard_normal(n),
                                   local_shapes=[(sz,) for sz in sizes])
    with pytest.raises(ValueError, match="slice-aligned"):
        Fr.matvec(bad)
    # non-divisible slice count (2P+1 slices over P): no scatter layout
    G2 = rng.standard_normal((2 * P + 1, 4, 3))
    Fr2 = MPIFredholm1(G2, nz=1, dtype=np.float64)
    assert Fr2.model_local_shapes is None
    with pytest.raises(ValueError, match="slice-aligned"):
        Fr2.matvec(DistributedArray.to_dist(
            rng.standard_normal(Fr2.shape[1])))


def test_fredholm_compute_dtype_c64(rng):
    """compute_dtype=complex64 halves the kernel's storage while the
    apply stays within c64 accuracy of the c128 operator (the
    MPIBlockDiag compute_dtype lever for the signal-processing hog)."""
    import jax.numpy as jnp
    nsl, nx, ny, nz = 8, 6, 5, 2
    G = (rng.standard_normal((nsl, nx, ny))
         + 1j * rng.standard_normal((nsl, nx, ny)))
    Op = MPIFredholm1(G, nz=nz, dtype=np.complex128)
    Oc = MPIFredholm1(G, nz=nz, dtype=np.complex128,
                      compute_dtype=jnp.complex64)
    # a complex kernel is stored as its (re, im) planes (PR 34): the
    # planes of complex64 are float32
    assert Oc.G.dtype == jnp.float32 and Oc.G.shape == (2, nsl, nx, ny)
    x = (rng.standard_normal(Op.shape[1])
         + 1j * rng.standard_normal(Op.shape[1]))
    dx = DistributedArray.to_dist(x, partition=Partition.BROADCAST)
    y128 = Op.matvec(dx).asarray()
    y64 = Oc.matvec(dx).asarray()
    rel = np.linalg.norm(y64 - y128) / np.linalg.norm(y128)
    assert 0 < rel < 1e-5  # c64-rounded but not garbage
    a128 = Op.rmatvec(Op.matvec(dx)).asarray()
    a64 = Oc.rmatvec(Oc.matvec(dx)).asarray()
    rel_a = np.linalg.norm(a64 - a128) / np.linalg.norm(a128)
    assert rel_a < 1e-5


def test_mdc_compute_dtype_passthrough(rng):
    """MPIMDC(compute_dtype=...) narrows the Fredholm kernel storage
    and stays accurate end-to-end."""
    import jax.numpy as jnp
    from pylops_mpi_tpu import MPIMDC
    ns, nr, nt, nv = 5, 4, 17, 1
    Gt = rng.standard_normal((ns, nr, nt))
    from pylops_mpi_tpu.models import kernel_to_frequency
    G = kernel_to_frequency(Gt)
    Op = MPIMDC(G, nt=nt, nv=nv, twosided=True)
    Oc = MPIMDC(G, nt=nt, nv=nv, twosided=True,
                compute_dtype=jnp.complex64)
    x = rng.standard_normal(Op.shape[1])
    dx = DistributedArray.to_dist(x, partition=Partition.BROADCAST)
    y = Op.matvec(dx).asarray()
    yc = Oc.matvec(dx).asarray()
    rel = np.linalg.norm(yc - y) / np.linalg.norm(y)
    assert rel < 1e-5


# ------------------------------------------- planar (complex-free) MDC
# The plane-pair chain ops/mdc.py builds on TPU runtimes without
# complex lowering (round-5 hardware finding): local FFTs via
# dft.rfft_planes (local.FFT(planes=True)), the Fredholm kernel stored
# and contracted as stacked real planes, no complex dtype anywhere.


def _rel(a, b):
    a = np.asarray(a).astype(np.complex128)
    b = np.asarray(b).astype(np.complex128)
    return float(np.linalg.norm((a - b).ravel())
                 / np.linalg.norm(b.ravel()))


def test_fredholm1_planar_matches_complex(rng):
    """MPIFredholm1(planar=True) on stacked (re, im) planes computes
    the same batched complex GEMM as the complex operator, forward and
    adjoint, with and without saveGt."""
    nsl, nx, ny, nz = 16, 5, 4, 2
    G = (rng.standard_normal((nsl, nx, ny))
         + 1j * rng.standard_normal((nsl, nx, ny)))
    m = (rng.standard_normal((nsl, ny, nz))
         + 1j * rng.standard_normal((nsl, ny, nz)))
    d = (rng.standard_normal((nsl, nx, nz))
         + 1j * rng.standard_normal((nsl, nx, nz)))
    Oc = MPIFredholm1(G, nz=nz, dtype=np.complex128)
    for saveGt in (False, True):
        Op = MPIFredholm1(G, nz=nz, saveGt=saveGt, dtype=np.float64,
                          planar=True)
        assert Op.dtype == np.float64  # real plane dtype
        assert Op.shape == (2 * Oc.shape[0], 2 * Oc.shape[1])
        dm = DistributedArray.to_dist(
            np.concatenate([m.real.ravel(), m.imag.ravel()]),
            partition=Partition.BROADCAST)
        got = np.asarray(Op.matvec(dm).asarray()).reshape(2, -1)
        want = Oc.matvec(DistributedArray.to_dist(
            m.ravel(), partition=Partition.BROADCAST)).asarray()
        assert _rel(got[0] + 1j * got[1], want) < 1e-12
        dd = DistributedArray.to_dist(
            np.concatenate([d.real.ravel(), d.imag.ravel()]),
            partition=Partition.BROADCAST)
        got = np.asarray(Op.rmatvec(dd).asarray()).reshape(2, -1)
        want = Oc.rmatvec(DistributedArray.to_dist(
            d.ravel(), partition=Partition.BROADCAST)).asarray()
        assert _rel(got[0] + 1j * got[1], want) < 1e-12


@pytest.mark.parametrize("conj", [False, True])
def test_mdc_planar_matches_complex_chain(rng, conj):
    """Acceptance: planar-mode MPIMDC (f32 planes) matches the complex
    chain to 1e-5 forward and adjoint — identical external shapes,
    real model/data on both ends."""
    from pylops_mpi_tpu import MPIMDC
    nt, nr, ns, nv, nfmax = 17, 4, 5, 2, 9
    G = (rng.standard_normal((nfmax, ns, nr))
         + 1j * rng.standard_normal((nfmax, ns, nr))).astype(np.complex64)
    Oc = MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=2.0, twosided=True,
                conj=conj, engine="complex")
    Op = MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=2.0, twosided=True,
                conj=conj, engine="planar")
    assert Op.shape == Oc.shape and Op.dtype == Oc.dtype
    x = rng.standard_normal(Oc.shape[1]).astype(np.float32)
    dx = DistributedArray.to_dist(x, partition=Partition.BROADCAST)
    assert _rel(Op.matvec(dx).asarray(), Oc.matvec(dx).asarray()) < 1e-5
    y = rng.standard_normal(Oc.shape[0]).astype(np.float32)
    dy = DistributedArray.to_dist(y, partition=Partition.BROADCAST)
    assert _rel(Op.rmatvec(dy).asarray(),
                Oc.rmatvec(dy).asarray()) < 1e-5


def test_mdc_planar_auto_select_and_complex_free(rng):
    """Under the planar fft mode (what auto resolves to on
    no-complex-lowering TPU runtimes) MPIMDC auto-builds the planar
    chain, and its compiled forward+adjoint programs contain zero
    complex-dtype ops."""
    from pylops_mpi_tpu import MPIMDC
    from pylops_mpi_tpu.ops import dft
    from pylops_mpi_tpu.utils.hlo import assert_complex_free
    nt, nr, ns, nv, nfmax = 17, 3, 4, 1, 9
    G = (rng.standard_normal((nfmax, ns, nr))
         + 1j * rng.standard_normal((nfmax, ns, nr))).astype(np.complex64)
    dft.set_fft_mode("planar")
    try:
        Op = MPIMDC(G, nt=nt, nv=nv, twosided=True)  # engine=None: auto
        ref = MPIMDC(G, nt=nt, nv=nv, twosided=True, engine="planar")
        assert Op.shape == ref.shape
        x = rng.standard_normal(Op.shape[1]).astype(np.float32)
        dx = DistributedArray.to_dist(x, partition=Partition.BROADCAST)
        assert_complex_free(lambda v: Op.matvec(v), dx)
        # auto == explicit planar, numerically
        assert _rel(Op.matvec(dx).asarray(),
                    ref.matvec(dx).asarray()) < 1e-6
        dy = DistributedArray.to_dist(
            rng.standard_normal(Op.shape[0]).astype(np.float32),
            partition=Partition.BROADCAST)
        assert_complex_free(lambda v: Op.rmatvec(v), dy)
    finally:
        dft.set_fft_mode(None)


def test_mdc_planar_inversion(rng):
    """The planar chain is a working operator end to end: CGLS recovers
    the model through it (the complex-chain inversion test, planar)."""
    from pylops_mpi_tpu import MPIMDC
    nt, nr, ns, nv = 17, 3, 4, 1
    nfft = int(np.ceil((nt + 1) / 2))
    G = (rng.standard_normal((nfft, ns, nr))
         + 1j * rng.standard_normal((nfft, ns, nr)))
    Op = MPIMDC(G, nt=nt, nv=nv, dt=1.0, dr=1.0, twosided=True,
                engine="planar")
    xtrue = rng.standard_normal(nt * nr * nv)
    dy = Op.matvec(DistributedArray.to_dist(
        xtrue, partition=Partition.BROADCAST))
    x0 = DistributedArray.to_dist(np.zeros(nt * nr * nv),
                                  partition=Partition.BROADCAST)
    x, *_ = cgls(Op, dy, x0, niter=300, tol=1e-14)
    np.testing.assert_allclose(x.asarray(), xtrue, rtol=1e-4, atol=1e-6)
