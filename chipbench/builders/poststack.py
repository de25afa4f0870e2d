"""Deployment builder ``poststack``: Laplacian-regularised 3-D
post-stack inversion (upstream ``tutorials/poststack.py``): the model
cube ``(ny, nx, nt0)`` sharded on inlines, modelling ``0.5 W D`` (a
stationary wavelet convolution after a centred first derivative, both
along time) in an ``MPIBlockDiag``, the regulariser ``sqrt(epsR)`` times
an ``MPILaplacian`` over all three axes, both in an ``MPIStackedVStack``
solved by CGLS from a background model.

The operator comes from the program's own entry point
(``pylops_mpi_tpu.models.poststack_regularized``; a program without it
stops here, at once). Everything else is the benchmark's own and imports
nothing from ``pylops_mpi_tpu.ops``, ``.solvers`` or ``.models``:

- **the wavelet**: Ricker, ``2 * ntwav_half - 1`` taps at ``dt``;
- **the model family**, made ON the device from the seed: a layered
  log-impedance cube (``layers`` smooth steps of seeded size and time,
  a few samples wide) whose horizons undulate and dip with inline and
  crossline (lateral structure), plus ``N(0, sigma)`` detail; the
  background ``x0`` is the model smoothed along time (a normalised
  Hann window of ``smooth`` samples); the data ``d`` is the builder's
  own plain modelling of the model;
- **the plain reference**: the convolution as one product with the
  filter's dense ``nt0 x nt0`` Toeplitz matrix, slice-and-pad derivative
  and Laplacian, each with its adjoint written out, under
  ``jax.default_matmul_precision("highest")``,
  textbook CGLS (the iteration of ``chipbench/reference.py``, with the
  stacked data kept as a pair of cubes) on the stacked system for the
  correction to ``x0`` — ``x = x0 + cgls(A, [d, 0] - A x0)``, which is
  CGLS started from ``x0`` — and the stacked residual's norm after the
  iterations over its first (the recurrence's own ``s``).

**Why "within tolerance of the reference" and not "of the true
model":** post-stack inversion is ill-posed (``W D`` has the constant
and everything outside the wavelet's band in its null space; the
unregularised normal equations have a condition number near 1e17), so
30 iterations from a smooth background recover the band-limited part
and stay far from the true model by design. The guarantee is therefore
agreement with a plain float32 solve of the same system, and that solve
bringing the residual down (``guarantees.resid_drop``).

**Why a second limit, on the same solve in its correction form**
(``guarantees.corr_tol``): at the tutorial's ``epsR`` the answer moves
0.03 % from ``x0`` in 30 iterations, most of it the regulariser's
smoothing, and it is stored in float32 beside a level of 8: the
program's own rounding of ``x`` (1.5e-7 of its norm) is as large as what
a convolution with bfloat16 products changes (2e-7; PERF.md section 6,
PR 32), so no limit on that answer can tell the two apart. CGLS from
``x0`` on ``[d, 0]`` IS CGLS from zero on the residual ``[d, 0] - A
x0``, iterate for iterate; solved in that form the answer is the
correction alone, float32 resolves it to 1e-7 of ITS norm, and a
convolution 3e-3 off shows at first order. So the loop also gives the
timed solver program — the same executable, which must not compile
again — every pool member's residual with a zero start, once in
set-up, and holds the correction to the plain reference's.

**Cost** (``chipbench/costs_poststack.py``): what one CGLS iteration's
operator work needs — six volume-sized streams, 354 flops an element.
The solver's own vector traffic is left out of the floor, as in the
other builders.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np


def ricker(n_half: int, dt: float, f0: float) -> np.ndarray:
    """Zero-phase Ricker wavelet of ``2 * n_half - 1`` taps."""
    t = np.arange(n_half) * dt
    t = np.concatenate([-t[:0:-1], t])
    a = (np.pi * f0 * t) ** 2
    return ((1 - 2 * a) * np.exp(-a)).astype(np.float32)


# ------------------------------------------------------ plain operators
def toeplitz(h, offset: int, n: int) -> np.ndarray:
    """The convolution as a dense ``(n, n)`` matrix acting on rows from
    the right: ``T[i, o] = h[o + offset - i]``, zero outside the
    filter."""
    h = np.asarray(h, dtype=np.float32)
    j = np.arange(n)[None, :] + offset - np.arange(n)[:, None]
    return np.where((j >= 0) & (j < len(h)),
                    h[np.clip(j, 0, len(h) - 1)], np.float32(0))


def conv_t(v, h, offset: int, cast=None):
    """``y[..., i] = sum_j h[j] v[..., i + offset - j]``, zero outside
    the array, as the plainest thing a chip does fast: one product with
    the dense Toeplitz matrix of the filter under precision
    ``highest`` (81 shifted slices with a multiply-add each, the other
    plain form, take 298 ms a volume on the chip against 20 — 93 s of
    set-up for two references; PERF.md section 6, PR 32). ``cast`` (a
    dtype) rounds both operands of every product to it, sums in
    float32 — how the account of a lower-precision convolution is
    made."""
    import jax
    import jax.numpy as jnp
    T = jnp.asarray(toeplitz(h, offset, v.shape[-1]))
    if cast is not None:
        return jnp.matmul(v.astype(cast), T.astype(cast),
                          preferred_element_type=v.dtype)
    return jnp.matmul(v, T, precision=jax.lax.Precision.HIGHEST)


def corr_t(v, h, offset: int, cast=None):
    """Adjoint of :func:`conv_t`: correlation with ``h``."""
    return conv_t(v, h[::-1], len(h) - 1 - offset, cast)


def _pad_ax(v, ax, before, after):
    import jax.numpy as jnp
    pw = [(0, 0)] * v.ndim
    pw[ax] = (before, after)
    return jnp.pad(v, pw)


def deriv_t(v):
    """Centred first derivative along time, one-sided at both ends
    (pylops ``FirstDerivative(kind="centered", edge=True)``)."""
    import jax.numpy as jnp
    return jnp.concatenate([v[..., 1:2] - v[..., 0:1],
                            0.5 * (v[..., 2:] - v[..., :-2]),
                            v[..., -1:] - v[..., -2:-1]], axis=-1)


def deriv_t_adj(u):
    import jax.numpy as jnp
    n = u.shape[-1]
    c = 0.5 * u[..., 1:-1]
    u0, u1 = u[..., 0:1], u[..., -1:]
    return (_pad_ax(c, -1, 2, 0) - _pad_ax(c, -1, 0, 2)
            + _pad_ax(jnp.concatenate([-u0, u0], -1), -1, 0, n - 2)
            + _pad_ax(jnp.concatenate([-u1, u1], -1), -1, n - 2, 0))


def _rows(v, ax, lo, hi):
    idx = [slice(None)] * v.ndim
    idx[ax] = slice(lo, hi)
    return v[tuple(idx)]


def laplacian(v):
    """Sum over the axes of the centred second difference, zero in the
    first and last row of each axis (pylops ``edge=False``)."""
    out = None
    for ax in range(v.ndim):
        n = v.shape[ax]
        core = _rows(v, ax, 0, n - 2) - 2.0 * _rows(v, ax, 1, n - 1) \
            + _rows(v, ax, 2, n)
        part = _pad_ax(core, ax, 1, 1)
        out = part if out is None else out + part
    return out


def laplacian_adj(u):
    out = None
    for ax in range(u.ndim):
        n = u.shape[ax]
        c = _rows(u, ax, 1, n - 1)
        part = _pad_ax(c, ax, 0, 2) - 2.0 * _pad_ax(c, ax, 1, 1) \
            + _pad_ax(c, ax, 2, 0)
        out = part if out is None else out + part
    return out


def plain_system(wav: np.ndarray, scale: float, cast=None, taps=None):
    """``(mv, rmv)`` of the stacked system ``[0.5 W D; scale * Lap]``
    on ``(ny, nx, nt0)`` cubes; the stacked data is a pair of cubes.
    ``cast`` and ``taps`` (keep only the central ``taps`` of the
    wavelet's, a truncated convolution) make the two deliberately wrong
    convolutions of the account in PERF.md; the timed path never passes
    them."""
    h = np.asarray(wav, dtype=np.float32)
    off = len(h) // 2
    if taps is not None:
        h = h.copy()
        h[:off - taps // 2] = 0.0
        h[off + taps // 2 + 1:] = 0.0

    def mv(c):
        return (0.5 * conv_t(deriv_t(c), h, off, cast), scale * laplacian(c))

    def rmv(s):
        return deriv_t_adj(0.5 * corr_t(s[0], h, off, cast)) \
            + scale * laplacian_adj(s[1])

    return mv, rmv


def cgls_pair(mv, rmv, y, niter: int):
    """Textbook CGLS from zero for data that is a PAIR of cubes: the
    iteration of ``chipbench/reference.py`` (two products and five
    vector updates, no stopping test) with the pair kept as a pair — a
    stacked ``(2, ...)`` array would cost two volumes a copy, which the
    chip has not got beside the pool."""
    import jax
    import jax.numpy as jnp

    def dot2(u):
        return jnp.sum(u[0] * u[0]) + jnp.sum(u[1] * u[1])

    r = rmv(y)
    c = r
    q = mv(c)

    def body(_, st):
        x, s, c, q, kold = st
        a = kold / dot2(q)
        x = x + a * c
        s = (s[0] - a * q[0], s[1] - a * q[1])
        r = rmv(s)
        k = jnp.sum(r * r)
        c = r + (k / kold) * c
        return x, s, c, mv(c), k

    x, s, *_ = jax.lax.fori_loop(
        0, niter, body, (jnp.zeros_like(r), y, c, q, jnp.sum(r * r)))
    return x, jnp.sqrt(dot2(s))


def plain_solve(wav, scale, niter: int, cast=None, taps=None):
    """``f(y0, y1, x0) -> (x, dx, r0, r1, drop)``: textbook CGLS from
    ``x0`` on the plain stacked system for the data ``(y0, y1)``
    (``y1=None``: zero) — the residual ``r = y - A x0``, which is the
    data of the same solve in its correction form; the correction
    ``dx`` = CGLS from zero on ``r``; ``x = x0 + dx``; and the
    residual's norm after ``niter`` iterations over its first."""
    import jax
    import jax.numpy as jnp

    mv, rmv = plain_system(wav, scale, cast, taps)

    def solve(y0, y1, x0):
        with jax.default_matmul_precision("highest"):
            a0 = mv(x0)
            r = (y0 - a0[0], -a0[1] if y1 is None else y1 - a0[1])
            first = jnp.sqrt(jnp.sum(r[0] * r[0]) + jnp.sum(r[1] * r[1]))
            dx, last = cgls_pair(mv, rmv, r, niter)
            return x0 + dx, dx, r[0], r[1], last / first

    return jax.jit(solve)


# the two deliberately wrong convolutions of the account (PERF.md
# section 6, PR 32): every product's operands rounded to bfloat16 (the
# nearest precision below the configuration's), and the wavelet cut to
# its central 31 taps
CONTROLS = {"bf16": {"cast": "bfloat16"}, "taps31": {"taps": 31}}


# ------------------------------------------------------ the model family
def make_case(sizes: dict, wav: np.ndarray):
    """``f(key) -> (d, x0)``, cubes ``(ny, nx, nt0)`` float32 made on
    the device: the plain modelling of the seeded model and the model's
    smoothed background (the model itself stays inside: no guarantee
    is stated against it)."""
    import jax
    import jax.numpy as jnp

    ny, nx, nt0 = (int(sizes[k]) for k in ("ny", "nx", "nt0"))
    ny_all = int(sizes.get("ny_deployment", ny))
    L, sigma = int(sizes["layers"]), float(sizes["sigma"])
    ns = int(sizes["smooth"]) | 1
    hann = np.hanning(ns + 2)[1:-1].astype(np.float32)
    hann /= hann.sum()
    h = np.asarray(wav, dtype=np.float32)

    def case(key):
        ks = jax.random.split(key, 5)
        step = 0.08 * jax.random.normal(ks[0], (L,), jnp.float32)
        when = jnp.sort(jax.random.uniform(ks[1], (L,), jnp.float32,
                                           0.03 * nt0, 0.97 * nt0))
        wide = jax.random.uniform(ks[2], (L,), jnp.float32, 1.0, 3.0)
        ph = jax.random.uniform(ks[3], (4,), jnp.float32, 0.0, 2 * np.pi)
        y = jnp.arange(ny, dtype=jnp.float32)[:, None, None] / ny_all
        x = jnp.arange(nx, dtype=jnp.float32)[None, :, None] / nx
        t = jnp.arange(nt0, dtype=jnp.float32)[None, None, :]
        # horizons undulate and dip: a time shift a trace
        tau = 0.02 * nt0 * (jnp.sin(2 * np.pi * 1.5 * y + ph[0])
                            + jnp.sin(2 * np.pi * 2.5 * x + ph[1])) \
            + 0.03 * nt0 * (y * jnp.cos(ph[2]) + x * jnp.cos(ph[3]))
        m = jnp.full((ny, nx, nt0), 8.0, jnp.float32)
        for i in range(L):
            m = m + step[i] * jnp.tanh((t - tau - when[i]) / wide[i])
        m = m + sigma * jax.random.normal(ks[4], (ny, nx, nt0), jnp.float32)
        one = jnp.ones((1, 1, nt0), jnp.float32)
        x0 = conv_t(m, hann, ns // 2) / conv_t(one, hann, ns // 2)
        d = 0.5 * conv_t(deriv_t(m), h, len(h) // 2)
        return d, x0

    return jax.jit(case)


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.models import poststack_regularized
    from chipbench import costs_poststack

    ny, nx, nt0 = (int(sizes[k]) for k in ("ny", "nx", "nt0"))
    wav = ricker(int(sizes["ntwav_half"]), float(sizes["dt"]),
                 float(sizes["f0"]))
    epsR = float(sizes["epsR"])
    scale = float(np.float32(np.sqrt(epsR)))

    t0 = time.perf_counter()
    StackOp, Op, LapOp = poststack_regularized(
        wav, nt0, (ny, nx), epsR, mesh=mesh, dtype=np.float32)
    construct_s = time.perf_counter() - t0
    V = ny * nx * nt0

    case = make_case(sizes, wav)

    def rhs(j: int, seed_: int):
        """Pool member ``j`` of the seed: ``(d, x0)`` as flat device
        arrays in the operator's own vector layout."""
        d, x0 = case(jax.random.fold_in(jax.random.key(int(seed_)), j))
        return d.ravel(), x0.ravel()

    niter = int(cfg["guarantees"]["niter"])
    ref = plain_solve(wav, scale, niter)

    def reference(d, x0, niter_: int) -> SimpleNamespace:
        """The plain solve of a pool member, flat: the answer ``x``,
        the correction ``dx = x - x0`` as CGLS made it (not their
        float32 difference), the correction form's data ``r = [d, 0] -
        A x0`` and the residual's ``drop``."""
        if int(niter_) != niter:
            raise ValueError(f"the guarantee is stated for {niter} "
                             "iterations")
        x, dx, r0, r1, drop = ref(d.reshape(ny, nx, nt0), None,
                                  x0.reshape(ny, nx, nt0))
        return SimpleNamespace(x=x.ravel(), dx=dx.ravel(), r0=r0.ravel(),
                               r1=r1.ravel(), drop=drop)

    def control(kind: str):
        """A deliberately wrong plain solve (``CONTROLS``) in the form
        the loop calls the program in — ``f(y, x0) -> x`` on the
        program's vectors — for ``dep.stand_in``: what shows that the
        loop's comparison refuses a lower-precision convolution."""
        wrong = plain_solve(wav, scale, niter, **CONTROLS[kind])
        # the answer alone comes out: beside the loop's references the
        # chip has no room for the solve's other four volumes
        answer = jax.jit(lambda y0, y1, x0: wrong(y0, y1, x0)[0].ravel())

        def solve(y, x0):
            y0, y1 = (c.array.reshape(ny, nx, nt0) for c in y.distarrays)
            return vector(answer(y0, y1, x0.array.reshape(ny, nx, nt0)))
        return solve

    def vector(a=None):
        """A model- or data-sized ``DistributedArray`` holding ``a``
        (zeros when not given)."""
        out = pmt.DistributedArray(global_shape=V, mesh=Op.mesh,
                                   local_shapes=Op.local_shapes_m,
                                   dtype=np.float32)
        if a is not None:
            out[:] = a
        return out

    return SimpleNamespace(
        op=StackOp, modelling=Op, regulariser=LapOp, mesh=mesh,
        nrows=2 * V, ncols=V, rhs=rhs, reference=reference, vector=vector,
        control=control, stand_in=None,
        corr_tol=float(cfg["guarantees"]["corr_tol"]),
        cost=lambda k=1: costs_poststack.iteration(sizes, len(wav)),
        conv_cost=lambda: costs_poststack.convolution(sizes, len(wav)),
        dtype="float32", resid_drop=float(cfg["guarantees"]["resid_drop"]),
        split={"construct_s": construct_s},
        describe=f"cube {ny}x{nx}x{nt0} float32 ({4 * V} bytes a volume), "
                 f"{len(wav)}-tap Ricker at {sizes['f0']} Hz, "
                 f"sqrt(epsR)={scale:g}, stacked system "
                 f"{type(StackOp).__name__}[{type(Op).__name__}, "
                 f"{type(LapOp).__name__}] on {int(mesh.devices.size)} "
                 "device(s)")
