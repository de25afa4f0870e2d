"""Tests for the local FFT engine seam (``ops/dft.py``).

The matmul (MXU) DFT engine exists for runtimes that ship no FFT
custom-call. The engine must match ``numpy.fft`` bit-for-tolerance across mixed-radix,
prime (Bluestein), power-of-two, padded/truncated, real and ortho-norm
cases, in both precisions, so that forcing
``PYLOPS_MPI_TPU_FFT_MODE=matmul`` is purely an execution-path choice.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pylops_mpi_tpu.ops import dft


def _rel(got, want):
    got = np.asarray(got).astype(np.complex128)
    want = np.asarray(want).astype(np.complex128)
    return float(np.linalg.norm((got - want).ravel())
                 / max(np.linalg.norm(want.ravel()), 1e-300))


def _force_matmul(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "matmul")


def _force_mode(monkeypatch, mode):
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", mode)


# both GEMM engines run every core-correctness case: the planar engine
# (re/im plane pairs, Karatsuba 3-GEMM stages) must be a pure
# execution-path choice exactly like the complex matmul engine
ENGINES = ["matmul", "planar"]


# sizes exercising each code path: GEMM base, mixed-radix composite,
# power of two, prime > base (Bluestein), and a ragged odd composite
SIZES = [8, 100, 128, 192, 256, 263, 1000, 1024]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("mode", ENGINES)
def test_fft_matches_numpy(mode, monkeypatch, n):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((3, n))
         + 1j * rng.standard_normal((3, n))).astype(np.complex64)
    assert _rel(dft.fft(jnp.asarray(x)), np.fft.fft(x)) < 2e-6
    assert _rel(dft.ifft(jnp.asarray(x)), np.fft.ifft(x)) < 2e-6


@pytest.mark.parametrize("n,nfft", [(100, 160), (100, 60), (128, 128)])
@pytest.mark.parametrize("mode", ENGINES)
def test_fft_pad_truncate(mode, monkeypatch, n, nfft):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(4)
    x = (rng.standard_normal((2, n))
         + 1j * rng.standard_normal((2, n))).astype(np.complex64)
    assert _rel(dft.fft(jnp.asarray(x), n=nfft),
                np.fft.fft(x, n=nfft)) < 2e-6


@pytest.mark.parametrize("axis", [0, 1, -1])
@pytest.mark.parametrize("mode", ENGINES)
def test_fft_axis(mode, monkeypatch, axis):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(5)
    x = (rng.standard_normal((24, 36))
         + 1j * rng.standard_normal((24, 36))).astype(np.complex64)
    assert _rel(dft.fft(jnp.asarray(x), axis=axis),
                np.fft.fft(x, axis=axis)) < 2e-6


@pytest.mark.parametrize("n,nfft", [(100, None), (100, 128), (101, 101),
                                    (64, 48)])
@pytest.mark.parametrize("mode", ENGINES)
def test_rfft_irfft(mode, monkeypatch, n, nfft):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(6)
    x = rng.standard_normal((3, n)).astype(np.float32)
    assert _rel(dft.rfft(jnp.asarray(x), n=nfft),
                np.fft.rfft(x, n=nfft)) < 2e-6
    nh = (nfft or n) // 2 + 1
    c = (rng.standard_normal((3, nh))
         + 1j * rng.standard_normal((3, nh))).astype(np.complex64)
    assert _rel(dft.irfft(jnp.asarray(c), n=nfft),
                np.fft.irfft(c, n=nfft)) < 2e-6


@pytest.mark.parametrize("mode", ENGINES)
def test_ortho_norm(mode, monkeypatch):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((2, 96))
         + 1j * rng.standard_normal((2, 96))).astype(np.complex64)
    assert _rel(dft.fft(jnp.asarray(x), norm="ortho"),
                np.fft.fft(x, norm="ortho")) < 2e-6
    assert _rel(dft.ifft(jnp.asarray(x), norm="ortho"),
                np.fft.ifft(x, norm="ortho")) < 2e-6
    xr = rng.standard_normal((2, 96)).astype(np.float32)
    assert _rel(dft.rfft(jnp.asarray(xr), norm="ortho"),
                np.fft.rfft(xr, norm="ortho")) < 2e-6


@pytest.mark.parametrize("mode", ENGINES)
def test_roundtrip(mode, monkeypatch):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(8)
    x = (rng.standard_normal((4, 263))
         + 1j * rng.standard_normal((4, 263))).astype(np.complex64)
    assert _rel(dft.ifft(dft.fft(jnp.asarray(x))), x) < 2e-6


@pytest.mark.slow  # exhaustive sweep: ~22 s over both engines; the
# non-slow smoke below keeps one representative of each factorization
# shape in the default run (VERDICT next #7: tier-1 wall budget)
@pytest.mark.parametrize("mode", ENGINES)
def test_every_small_n(mode, monkeypatch):
    """Exhaustive n=1..64: every factorization shape (1, primes, prime
    powers, mixed composites) through the engine in one compile-free
    sweep — factorization bugs hide in small sizes."""
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(11)
    for n in range(1, 65):
        x = (rng.standard_normal((2, n))
             + 1j * rng.standard_normal((2, n))).astype(np.complex64)
        assert _rel(dft.fft(jnp.asarray(x)), np.fft.fft(x)) < 5e-6, n


@pytest.mark.parametrize("mode", ENGINES)
def test_small_n_smoke(mode, monkeypatch):
    """Fast stand-in for the exhaustive small-n sweep: one n per
    factorization shape (unit, prime, prime power, even/odd mixed
    composite, GEMM-base boundary)."""
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(11)
    for n in (1, 2, 7, 9, 12, 31, 45, 64):
        x = (rng.standard_normal((2, n))
             + 1j * rng.standard_normal((2, n))).astype(np.complex64)
        assert _rel(dft.fft(jnp.asarray(x)), np.fft.fft(x)) < 5e-6, n


@pytest.mark.parametrize("mode", ENGINES)
def test_large_prime_and_prime_power(mode, monkeypatch):
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(12)
    for n in (131, 169, 243, 512):  # prime>128, 13², 3⁵, 2⁹
        x = (rng.standard_normal((2, n))
             + 1j * rng.standard_normal((2, n))).astype(np.complex64)
        assert _rel(dft.fft(jnp.asarray(x)), np.fft.fft(x)) < 5e-6, n


def test_mode_validation(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "nonsense")
    with pytest.raises(ValueError, match="PYLOPS_MPI_TPU_FFT_MODE"):
        dft.fft_mode()


def test_auto_mode_cpu_uses_xla(monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_FFT_MODE", raising=False)
    # tests run on the forced-CPU backend: auto must pick xla there
    assert dft.use_matmul_fft() is False


def test_x64_precision(monkeypatch):
    _force_matmul(monkeypatch)
    from pylops_mpi_tpu.utils import deps
    if not deps.x64_enabled():
        import jax
        if not jax.config.jax_enable_x64:
            pytest.skip("x64 disabled in this session")
    rng = np.random.default_rng(9)
    x = (rng.standard_normal((2, 192))
         + 1j * rng.standard_normal((2, 192))).astype(np.complex128)
    assert _rel(dft.fft(jnp.asarray(x)), np.fft.fft(x)) < 1e-12


def test_gemm_base_platform_default(monkeypatch):
    """The mixed-radix base resolves per platform (128 on TPU for the
    MXU tile, 16 elsewhere) and obeys the env override."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_DFT_BASE", raising=False)
    dft._base_cache = None
    assert dft._gemm_base() == 16  # tests run on the CPU backend
    monkeypatch.setenv("PYLOPS_MPI_TPU_DFT_BASE", "64")
    dft._base_cache = None
    assert dft._gemm_base() == 64
    assert dft._best_split(1024) == 64


def test_stage_radices_accounting(monkeypatch):
    """stage_radices is the engine's work model: products must
    reconstruct the length, Bluestein sizes report 3 transforms of the
    pow2 convolution length, and the base caps every radix."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_DFT_BASE", raising=False)
    dft._base_cache = None
    base = dft._gemm_base()
    for n in (8, 100, 128, 1000, 1024):
        rs = dft.stage_radices(n)
        assert int(np.prod(rs)) == n, (n, rs)
        assert all(r <= base for r in rs)
    # prime beyond the base: 2 on-device pow2 transforms of m >= 2n-1
    # (the chirp kernel's spectrum is precomputed on the host)
    rs = dft.stage_radices(263)
    m = 1
    while m < 2 * 263 - 1:
        m *= 2
    assert len(rs) == 2 * len(dft.stage_radices(m))


@pytest.mark.parametrize("mode", ENGINES)
def test_packed_rfft_matches_numpy_all_norms(mode, monkeypatch):
    """The packed-real path (even n) across every norm, plus the odd-n
    fallback and n-argument pad/truncate — BOTH GEMM engines: the
    planar engine's norm scaling and half-spectrum pad/truncate are
    what FFT-less TPU runtimes actually run."""
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(11)
    for n in (10, 96, 101):
        x = rng.standard_normal((3, n))
        for norm in (None, "ortho", "forward"):
            got = np.asarray(dft.rfft(jnp.asarray(x), norm=norm))
            assert _rel(got, np.fft.rfft(x, norm=norm)) < 1e-10
            X = np.fft.rfft(x, norm=norm)
            got = np.asarray(dft.irfft(jnp.asarray(X), norm=norm))
            # numpy irfft defaults to n=2*(nh-1) (even) — compare there
            assert _rel(got, np.fft.irfft(X, norm=norm)) < 1e-10
    # pad + truncate through the packed path
    x = rng.standard_normal((2, 10))
    assert _rel(np.asarray(dft.rfft(jnp.asarray(x), n=16)),
                np.fft.rfft(x, n=16)) < 1e-10
    X = np.fft.rfft(rng.standard_normal((2, 24)))
    assert _rel(np.asarray(dft.irfft(jnp.asarray(X), n=16)),
                np.fft.irfft(X, n=16)) < 1e-10


# ----------------------------------------------------- planar plane-pair API

def test_planes_api_no_complex_input(monkeypatch):
    """The ``*_planes`` functions take and return REAL plane pairs —
    the API distributed kernels use to stay complex-free end to end."""
    _force_mode(monkeypatch, "planar")
    rng = np.random.default_rng(21)
    x = (rng.standard_normal((3, 96))
         + 1j * rng.standard_normal((3, 96))).astype(np.complex64)
    yr, yi = dft.fft_planes(jnp.asarray(x.real), jnp.asarray(x.imag))
    assert not jnp.iscomplexobj(yr) and not jnp.iscomplexobj(yi)
    assert _rel(np.asarray(yr) + 1j * np.asarray(yi), np.fft.fft(x)) < 2e-6
    zr, zi = dft.ifft_planes(yr, yi)
    assert _rel(np.asarray(zr) + 1j * np.asarray(zi), x) < 2e-6


def test_planes_rfft_irfft_roundtrip(monkeypatch):
    _force_mode(monkeypatch, "planar")
    rng = np.random.default_rng(22)
    x = rng.standard_normal((2, 100)).astype(np.float32)
    hr, hi = dft.rfft_planes(jnp.asarray(x))
    want = np.fft.rfft(x)
    assert _rel(np.asarray(hr) + 1j * np.asarray(hi), want) < 2e-6
    back = dft.irfft_planes(hr, hi, n=100)
    assert not jnp.iscomplexobj(back)
    assert _rel(np.asarray(back), x) < 2e-6


def test_planes_fft_none_imag(monkeypatch):
    """``xi=None`` means a zero imaginary plane (real input)."""
    _force_mode(monkeypatch, "planar")
    rng = np.random.default_rng(23)
    x = rng.standard_normal((2, 64)).astype(np.float32)
    yr, yi = dft.fft_planes(jnp.asarray(x), None)
    assert _rel(np.asarray(yr) + 1j * np.asarray(yi), np.fft.fft(x)) < 2e-6


def test_planar_under_jit(monkeypatch):
    """The planar engine must trace cleanly (it is called inside the
    pencil shard_map kernels)."""
    import jax
    _force_mode(monkeypatch, "planar")
    rng = np.random.default_rng(24)
    x = (rng.standard_normal((2, 60))
         + 1j * rng.standard_normal((2, 60))).astype(np.complex64)
    got = jax.jit(lambda v: dft.fft(v))(jnp.asarray(x))
    assert _rel(got, np.fft.fft(x)) < 2e-6


def test_planar_mode_accepted(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "planar")
    assert dft.fft_mode() == "planar"
    from pylops_mpi_tpu.ops import dft as _d
    _d.set_fft_mode("planar")
    assert _d.resolved_mode() == "planar"
    # use_matmul_fft: True for BOTH GEMM engines (callers use it for
    # tolerance/flop accounting, identical between the two)
    assert _d.use_matmul_fft() is True
    _d.set_fft_mode(None)


@pytest.mark.parametrize("n", [16, 15])
@pytest.mark.parametrize("mode", ENGINES)
def test_irfft_dc_nyquist_imag_leak(mode, monkeypatch, n):
    """numpy semantics: irfft treats the DC (and, for even n, Nyquist)
    bins as real — nonzero imaginary parts there must NOT leak into the
    output. Both GEMM engines, even (packed untangle) and odd
    (Hermitian-rebuild fallback) lengths."""
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(31)
    nh = n // 2 + 1
    X = (rng.standard_normal((3, nh))
         + 1j * rng.standard_normal((3, nh)))  # imag at bins 0 and -1
    for norm in (None, "ortho", "forward"):
        got = np.asarray(dft.irfft(jnp.asarray(X), n=n, norm=norm))
        assert _rel(got, np.fft.irfft(X, n=n, norm=norm)) < 1e-10, \
            (n, norm)


@pytest.mark.parametrize("mode", ENGINES)
def test_irfft_pad_truncate_all_norms(mode, monkeypatch):
    """Half-spectrum pad/truncate (n argument) through both GEMM
    engines across every norm."""
    _force_mode(monkeypatch, mode)
    rng = np.random.default_rng(32)
    X = (rng.standard_normal((2, 13))
         + 1j * rng.standard_normal((2, 13)))
    for n in (16, 32, 20, 11):
        for norm in (None, "ortho", "forward"):
            got = np.asarray(dft.irfft(jnp.asarray(X), n=n, norm=norm))
            assert _rel(got, np.fft.irfft(X, n=n, norm=norm)) < 1e-10, \
                (n, norm)


def test_planes_int_input_promotes_to_f64(monkeypatch):
    """Integer inputs promote through the COMPLEX result type (x64
    jnp.fft semantics: int64 -> complex128), so the planar engine must
    put them on float64 planes — not the float32 the raw storage dtype
    maps to."""
    from pylops_mpi_tpu.utils import deps
    import jax
    if not jax.config.jax_enable_x64:
        pytest.skip("x64 disabled in this session")
    _force_mode(monkeypatch, "planar")
    x = np.arange(24, dtype=np.int64).reshape(2, 12)
    hr, hi = dft.rfft_planes(jnp.asarray(x))
    assert hr.dtype == np.float64 and hi.dtype == np.float64
    assert _rel(np.asarray(hr) + 1j * np.asarray(hi),
                np.fft.rfft(x)) < 1e-12
    back = dft.irfft_planes(hr, hi, n=12)
    assert back.dtype == np.float64
    assert _rel(np.asarray(back), x) < 1e-12
    # the complex-signature wrapper agrees end to end
    assert np.asarray(dft.rfft(jnp.asarray(x))).dtype == np.complex128
    # plane_dtype is the public statement of the rule
    assert dft.plane_dtype(np.int64) == "float64"
    assert dft.plane_dtype(np.float32) == "float32"
    assert dft.plane_dtype(np.complex128) == "float64"
    assert dft.plane_dtype(np.float16) == "float32"
