"""Deployment builder ``mdd``: multi-dimensional deconvolution (upstream
``tutorials/mdd.py``): the frequency-domain kernel ``G (nfmax, ns, nr)``
sharded over frequency, the time-domain model ``(nt, nr, nv)`` and data
``(nt, ns, nv)`` replicated (``Partition.BROADCAST``), the operator
``MPIMDC = F1^H I1^H (dr dt sqrt(nt) G) I F`` — a real FFT along time,
a cut to the first ``nfmax`` frequencies, one complex matrix product a
frequency, the zero-padded way back — inverted by CGLS from zero.

The operator comes from the program's own entry point, with upstream's
arguments and nothing else: ``pmt.MPIMDC(G, nt=nt, nv=nv, dt=dt, dr=dr,
twosided=True)``. Everything else is the benchmark's own and imports
nothing from ``pylops_mpi_tpu.ops``, ``.solvers`` or ``.models``.

**One departure in what is handed over, forced by the chip's compiler
(PERF.md section 6, PR 34): the kernel is made, and given to the
program, as its real and imaginary PLANES** — one float32 array ``(2,
nfmax, ns, nr)``, the same 8 bytes an element. A TPU holds no complex
array: XLA splits a complex64 program argument into two float32 arrays
at every program's entry, so a complex64 kernel of 8.59 GB costs
another 8.59 GB of temporaries in every program that touches it
(compiled for a v5e: 16.44 of 15.75 GB). The reference reads the same
stored planes.

- **the kernel family**, made ON the device from the seed: at frequency
  ``f_k = k / (nt dt)``, ``G_k = w(f_k) (D_k + sigma N_k / sqrt(nr))``:
  ``w`` the amplitude spectrum of a Ricker wavelet of ``f0`` Hz (peak
  1), ``D_k = diag(exp(-2 pi i f_k tau_s))`` a direct arrival with a
  seeded delay a source-receiver pair, ``N_k`` complex standard normal.
  Every frequency's matrix has its singular values within ``w (1 -+ 2
  sigma)``: condition at most 3 at ``sigma`` = 0.25; ACROSS frequencies
  the wavelet's spectrum sets the scale (zero at 0 Hz: the band limit
  that makes MDD ill-posed outside the band);
- **the reflection response**: ``events`` arrivals a virtual source
  with seeded time, amplitude and linear moveout from the virtual
  source's receiver, plus ``N(0, noise)`` detail, shaped by the same
  wavelet in the frequency domain, brought to time by the adjoint real
  FFT; **the data** is the builder's own plain modelling of it;
- **the plain reference** (``plain_system``, ``plain_solve``):
  ``ifftshift`` along time, ``jnp.fft.rfft`` in pylops' real-FFT
  convention — ``norm="ortho"`` and the bins that have a conjugate
  twin (all but 0 for an odd ``nt``) scaled by sqrt(2), so that the
  half spectrum is an isometry of the real signal; its adjoint scales
  them by 1/sqrt(2) and lets ``irfft`` supply the twins — the first
  ``nfmax`` bins, ``einsum("fsr,frv->fsv")`` — as its four real
  products on the planes — under
  ``jax.default_matmul_precision("highest")``, the zero-padded way
  back; the adjoint chain written out likewise; textbook CGLS
  (``chipbench/reference.py``) from zero. It sweeps ``G`` twice an
  iteration, like the program.

**Why "within tolerance of the reference" and not "of the true
response":** the chip's share holds 64 of 512 frequency bins and the
wavelet is zero at 0 Hz, so the operator has a null space by design;
30 iterations from zero recover the part of the response the band
holds. The answer here IS the correction (the start is zero), so
float32 resolves it and one limit on the answer feels the product's
precision (the control below).

**Cost** (``chipbench/costs_mdd.py``): the kernel once an iteration at
its stored 8 bytes plus four vector streams; flops both products and
the four FFTs.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

# the deliberately wrong plain solve of the account (PERF.md section 6,
# PR 34): both operands of every product of the Fredholm integral
# rounded to bfloat16, the nearest precision below the configuration's
CONTROLS = {"bf16": {"cast": "bfloat16"}}


def ricker_spectrum(f, f0: float):
    """Amplitude spectrum of a Ricker wavelet of peak frequency ``f0``,
    normalised to 1 at ``f0``; zero at 0 Hz."""
    import jax.numpy as jnp
    a = (f / f0) ** 2
    return a * jnp.exp(1.0 - a)


def scale_of(sizes: dict) -> float:
    """pylops' MDC prescaling ``dr * dt * sqrt(nt)``."""
    return float(sizes["dr"]) * float(sizes["dt"]) \
        * float(np.sqrt(int(sizes["nt"])))


# ------------------------------------------------------ plain operators
def rfft_t(v, nfft: int, shift: bool):
    """pylops' real FFT along axis 0: ``(nt, ...)`` real to ``(nfft,
    ...)`` complex, orthonormal, the bins with a conjugate twin scaled
    by sqrt(2); ``shift``: ``ifftshift`` first (the two-sided model)."""
    import jax.numpy as jnp
    nt = v.shape[0]
    if shift:
        v = jnp.fft.ifftshift(v, axes=0)
    y = jnp.fft.rfft(v, axis=0, norm="ortho")
    k = jnp.arange(nfft)
    twin = (k >= 1) & (k < (nfft - 1 if nt % 2 == 0 else nfft))
    fac = jnp.where(twin, np.float32(np.sqrt(2.0)), np.float32(1.0))
    return y * fac.reshape((nfft,) + (1,) * (v.ndim - 1))


def rfft_t_adj(y, nt: int, shift: bool):
    """Adjoint of :func:`rfft_t` (the real inner product): the twinned
    bins scaled by 1/sqrt(2), ``irfft`` supplying their twins."""
    import jax.numpy as jnp
    nfft = y.shape[0]
    k = jnp.arange(nfft)
    twin = (k >= 1) & (k < (nfft - 1 if nt % 2 == 0 else nfft))
    fac = jnp.where(twin, np.float32(1.0 / np.sqrt(2.0)), np.float32(1.0))
    v = jnp.fft.irfft(y * fac.reshape((nfft,) + (1,) * (y.ndim - 1)),
                      n=nt, axis=0, norm="ortho")
    return jnp.fft.fftshift(v, axes=0) if shift else v


def _product(P, v, spec: str, cast=None):
    """``einsum(spec, G, v)`` for the kernel's planes ``P`` and a
    complex ``v``, as the four real products of the textbook:
    ``(Gr vr - Gi vi) + i (Gr vi + Gi vr)``. Not ``jnp.einsum`` on the
    joined planes: XLA's complex product (three real ones, Gauss) keeps
    ``Gr + Gi``, a 4.29 GB temporary, for the length of the solve
    (compiled for a v5e: 15.3 of 15.75 GB).

    ``cast="bfloat16"`` (the control): both operands of each product
    rounded to bfloat16, the sum in float32. On a TPU that is the MXU's
    own single pass, ``Precision.DEFAULT`` — written as casts the
    compiler rounds the WHOLE kernel first, out of every loop (4 GB
    and two 2 GB planes beside the kernel: 17.88 of 15.75 GB, my chip
    run, PR 34); elsewhere, where ``DEFAULT`` is a float32 product, the
    casts."""
    import jax
    import jax.numpy as jnp
    vr, vi = jnp.real(v), jnp.imag(v)
    if cast is None:
        e = lambda a, b: jnp.einsum(spec, a, b)
    elif jax.default_backend() == "tpu":
        assert cast == "bfloat16"
        e = lambda a, b: jnp.einsum(spec, a, b,
                                    precision=jax.lax.Precision.DEFAULT)
    else:
        e = lambda a, b: jnp.einsum(spec, a.astype(cast), b.astype(cast),
                                    preferred_element_type=jnp.float32)
    return jax.lax.complex(e(P[0], vr) - e(P[1], vi),
                           e(P[0], vi) + e(P[1], vr))


def plain_system(sizes: dict, cast=None):
    """``(mv, rmv)`` of the chip's share of MDC on ``(nt, nr, nv)`` /
    ``(nt, ns, nv)`` real arrays, each taking the kernel's planes
    ``P`` first. Departures from upstream's description, each forced by
    holding an 8.59 GB kernel once: the planes (module docstring); the
    factor ``dr dt sqrt(nt)`` multiplies the product's spectrum, not a
    second, prescaled kernel; the adjoint contracts the other axis of
    the same kernel and conjugates the spectra around it, ``G^H u =
    conj(G^T conj(u))``, not a stored ``conj(G^T)``."""
    import jax.numpy as jnp
    nf, nt = int(sizes["nfmax"]), int(sizes["nt"])
    nfft = nt // 2 + 1
    a = np.float32(scale_of(sizes))

    def pad(y):
        return jnp.pad(y, ((0, nfft - nf), (0, 0), (0, 0)))

    def mv(P, m):
        s = rfft_t(m, nfft, shift=True)[:nf]
        return rfft_t_adj(pad(a * _product(P, s, "fsr,frv->fsv", cast)),
                          nt, shift=False)

    def rmv(P, d):
        s = jnp.conj(rfft_t(d, nfft, shift=False)[:nf])
        return rfft_t_adj(pad(a * jnp.conj(
            _product(P, s, "fsr,fsv->frv", cast))), nt, shift=True)

    return mv, rmv


def plain_solve(sizes: dict, niter: int, cast=None):
    """``f(P, d) -> (x, drop)``: textbook CGLS from zero on the plain
    system for the data ``d (nt, ns, nv)``, and the residual's norm
    after ``niter`` iterations over the data's (its first).

    THREE programs — the solve, the residual vector of its answer, the
    two norms — because of a fault of the chip's compiler: with the
    residual's norm reduced in the program that makes the residual
    (after the loop inside the solve's, or in one of its own) it read
    0.3537 = sqrt(1/8) whatever the seed at ``ns`` = ``nr`` = 4,096,
    for an answer whose residual, made as an array by one program and
    reduced by another, reads 1.7e-3 — as every smaller size and the
    CPU do (my chip runs, PR 34; PERF.md section 6)."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference

    mv, rmv = plain_system(sizes, cast)

    @jax.jit
    def solve(P, d):
        with jax.default_matmul_precision("highest"):
            return reference.cgls(lambda c: mv(P, c), lambda s: rmv(P, s),
                                  lambda u: jnp.sum(u * u), d, niter)

    @jax.jit
    def residual(P, d, x):
        with jax.default_matmul_precision("highest"):
            return d - mv(P, x)

    share = jax.jit(lambda r, d: jnp.sqrt(jnp.sum(r * r) / jnp.sum(d * d)))

    def drop(P, d, x):
        return share(residual(P, d, x), d)

    def both(P, d):
        x = solve(P, d)
        return x, drop(P, d, x)

    both.solve, both.drop = solve, drop
    return both


# ----------------------------------------------------------- the family
def make_kernel(sizes: dict):
    """``f(key) -> P``: the chip's share of the kernel as planes
    ``(2, nfmax, ns, nr)`` float32, made on the device in one program
    (module docstring)."""
    import jax
    import jax.numpy as jnp
    nf, ns, nr, nt = (int(sizes[k]) for k in ("nfmax", "ns", "nr", "nt"))
    df = 1.0 / (nt * float(sizes["dt"]))
    f0, sigma = float(sizes["f0"]), float(sizes["sigma"])
    tau_max = float(sizes["tau_max"])

    def kernel(key):
        kn, kt = jax.random.split(key)
        f = df * jnp.arange(nf, dtype=jnp.float32)
        w = ricker_spectrum(f, f0)[None, :, None, None]
        tau = jax.random.uniform(kt, (ns,), jnp.float32, 0.0, tau_max)
        ph = -2.0 * np.pi * f[:, None] * tau[None, :]        # (nf, ns)
        diag = jnp.stack([jnp.cos(ph), jnp.sin(ph)])[..., None]
        pair = jnp.arange(ns)[:, None] == jnp.arange(nr)[None, :]
        D = jnp.where(pair, diag, np.float32(0))
        N = jax.random.normal(kn, (2, nf, ns, nr), jnp.float32)
        return w * (D + np.float32(sigma / np.sqrt(2.0 * nr)) * N)

    return jax.jit(kernel)


def make_response(sizes: dict):
    """``f(key) -> x (nt, nr, nv)``: the seeded reflection response in
    time (module docstring)."""
    import jax
    import jax.numpy as jnp
    nf, nr, nt, nv = (int(sizes[k]) for k in ("nfmax", "nr", "nt", "nv"))
    dt, dr = float(sizes["dt"]), float(sizes["dr"])
    df = 1.0 / (nt * dt)
    E, noise = int(sizes["events"]), float(sizes["noise"])
    nfft = nt // 2 + 1

    def response(key):
        ks = jax.random.split(key, 5)
        f = df * jnp.arange(nf, dtype=jnp.float32)
        w = ricker_spectrum(f, float(sizes["f0"]))[:, None, None]
        t0 = jax.random.uniform(ks[0], (E,), jnp.float32, 0.05, 0.4) \
            * (nt // 2) * dt
        amp = jax.random.normal(ks[1], (E,), jnp.float32)
        slow = jax.random.uniform(ks[2], (E,), jnp.float32, 2e-4, 6e-4)
        at = jnp.arange(nv) * (nr // nv)           # the source's receiver
        off = dr * jnp.abs(jnp.arange(nr)[:, None] - at[None, :])
        t = t0[:, None, None] + slow[:, None, None] * off[None]
        ph = -2.0 * np.pi * f[:, None, None, None] * t[None]
        a = amp[None, :, None, None]
        X = jax.lax.complex(jnp.sum(a * jnp.cos(ph), 1),
                            jnp.sum(a * jnp.sin(ph), 1))
        X = X + noise * jax.lax.complex(
            jax.random.normal(ks[3], (nf, nr, nv), jnp.float32),
            jax.random.normal(ks[4], (nf, nr, nv), jnp.float32))
        return rfft_t_adj(jnp.pad(w * X, ((0, nfft - nf), (0, 0), (0, 0))),
                          nt, shift=True)

    return jax.jit(response)


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp
    import pylops_mpi_tpu as pmt
    from chipbench import costs_mdd

    nf, ns, nr, nt, nv = (int(sizes[k])
                          for k in ("nfmax", "ns", "nr", "nt", "nv"))
    dt, dr = float(sizes["dt"]), float(sizes["dr"])
    key = jax.random.key(int(seed))

    t0 = time.perf_counter()
    P = jax.block_until_ready(make_kernel(sizes)(jax.random.fold_in(key, 0)))
    generate_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    # upstream's arguments and nothing else; the kernel as its planes
    Op = pmt.MPIMDC(P, nt=nt, nv=nv, dt=dt, dr=dr, twosided=True)
    construct_s = time.perf_counter() - t0

    response = make_response(sizes)
    mv, _ = plain_system(sizes)
    model = jax.jit(lambda p, x: mv(p, x).ravel())

    def rhs(j: int, seed_: int):
        """Pool member ``j`` of the seed: the data ``d``, flat, a device
        array in the operator's own vector layout."""
        k = jax.random.fold_in(jax.random.key(int(seed_)), 1 + j)
        with jax.default_matmul_precision("highest"):
            return model(P, response(k))

    niter = int(cfg["guarantees"]["niter"])
    ref = plain_solve(sizes, niter)

    def reference(d, niter_: int) -> SimpleNamespace:
        """The plain solve of a pool member, flat: the answer ``x`` and
        the residual's ``drop``."""
        if int(niter_) != niter:
            raise ValueError(f"the guarantee is stated for {niter} "
                             "iterations")
        x, drop = ref(P, d.reshape(nt, ns, nv))
        return SimpleNamespace(x=x.ravel(), drop=drop)

    def drop(d, x):
        """The residual of an answer ``x`` for the data ``d`` (flat)
        over the data's norm, by the plain forward in a program of its
        own: what holds the PROGRAM's answers to ``resid_drop`` too."""
        return ref.drop(P, d.reshape(nt, ns, nv), x.reshape(nt, nr, nv))

    def vector(n: int, a=None):
        """A ``Partition.BROADCAST`` vector of ``n`` float32 holding
        ``a`` (zeros when not given): what upstream's tutorial makes of
        its model and data."""
        out = pmt.DistributedArray(global_shape=n, mesh=mesh,
                                   partition=pmt.Partition.BROADCAST,
                                   dtype=np.float32)
        if a is not None:
            out[:] = a
        return out

    def control(kind: str):
        """A deliberately wrong plain solve (``CONTROLS``) in the form
        the loop calls the program in — ``f(y, x0) -> x`` on the
        program's vectors — for ``dep.stand_in``: what shows that the
        loop's comparison refuses a product at a lower precision."""
        wrong = plain_solve(sizes, niter, **CONTROLS[kind]).solve

        def solve(y, x0):
            return vector(nt * nr * nv,
                          wrong(P, y.array.reshape(nt, ns, nv)).ravel())
        return solve

    return SimpleNamespace(
        op=Op, mesh=mesh, nrows=nt * ns * nv, ncols=nt * nr * nv,
        rhs=rhs, reference=reference, drop=drop, vector=vector,
        control=control,
        stand_in=None, kernel=P,
        cost=lambda k=1: costs_mdd.iteration(sizes),
        fredholm_cost=lambda: costs_mdd.fredholm(sizes),
        dtype="float32", resid_drop=float(cfg["guarantees"]["resid_drop"]),
        split={"generate_s": generate_s, "construct_s": construct_s},
        describe=f"kernel {nf}x{ns}x{nr} complex64 as planes "
                 f"({8 * nf * ns * nr} bytes), model {nt}x{nr}x{nv} and "
                 f"data {nt}x{ns}x{nv} float32 BROADCAST "
                 f"({4 * nt * ns * nv} bytes a vector), dt {dt} s, dr "
                 f"{dr} m, Ricker {sizes['f0']} Hz, {type(Op).__name__} "
                 f"on {int(mesh.devices.size)} device(s)")
