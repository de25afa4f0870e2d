"""Deployment builder ``lsm``: least-squares Kirchhoff migration of a 2-D
line (upstream ``tutorials/lsm.py``): every shard builds the Kirchhoff
demigration of its batch of shots, the shards are stacked with
``MPIVStack``, the reflectivity is replicated (``Partition.BROADCAST``),
the data scattered over shots (``Partition.SCATTER``), and CGLS inverts
from zero.

The operator comes from the program's own entry point, with upstream's
arguments and nothing else: ``pmt.models.MPILSM(z, x, t, sources, recs,
vel, wav, wavc)``. Everything else is the benchmark's own and imports
nothing from ``pylops_mpi_tpu.ops``, ``.solvers`` or ``.models``.

**The operator, written out** (PyLops' static Kirchhoff, as ``LSM``
builds it by default): for a source-receiver pair ``p = (s, r)`` and a
pixel ``x``, ``T = (t_s(x) + t_r(x)) / dt`` with straight rays in a
constant velocity, ``i = floor(T)``, ``tau = T - i``, and where
``0 <= i < nt - 1``

    spray:   y[p, i] += (1 - tau) m[x];  y[p, i + 1] += tau m[x];   d[p, :] = w * y[p, :]
    adjoint: m[x] = sum_p (1 - tau) z[p, i] + tau z[p, i + 1],      z[p, :] = w (*) d[p, :]

``w`` a Ricker wavelet of ``f0`` Hz and ``nwav`` samples.

**Nothing is shared with the program but the survey.** The reference
makes its OWN per-point travel times from the geometry
(:func:`point_times`: ``(ns + nr) x npix`` float32 on the device, 0.55
GB at the configuration's sizes, in its own pixel order) and derives
``(i, tau)`` a block of ``PAIR_BLOCK`` pairs at a time
(:func:`pair_tables`) — no pair-sized table is stored, and no array,
layout or attribute of the program's operator is read: a source,
receiver or pixel the program misplaces is on one side only, and
``correct`` refuses it (``tests/test_lsm_deployment.py``). Where the
two sides' float32 travel times round to either side of a whole sample
the interpolation is continuous (``i, tau = 1`` is ``i + 1, tau = 0``).

- **the plain reference** (``plain_spray``, ``banded_spray``,
  ``plain_system``, ``plain_solve``): float32 under
  ``jax.default_matmul_precision("highest")``; the wavelet as ``nwav``
  shifted sums; textbook CGLS (``chipbench/reference.py``) from zero.
  The indexed part has TWO plain forms. ``plain_spray`` is the
  equations as they stand: ``.at[].add`` of the two taps, the gather
  by indexing. On the chip XLA's scatter and gather take 8.7 ns an
  entry: 2.35 s a shot an apply, 18.8 s an apply of the cell, 400 s a
  ten-iteration reference solve (my chip run, PR 38) — no set-up can
  afford two. So the solves run ``banded_spray``: each run of ``RUN``
  pixels of a pair is compared against the ``width`` samples from its
  own smallest index on (a one-hot over a short band, summed by
  ``jnp.sum``), and only the strips of ``width`` samples are scattered
  or gathered — 32 times fewer indexed entries. ``band_width`` reads
  the width from the reference's own travel times; the form shares no
  code with the program and is held to ``plain_spray`` entry for entry
  and to the equations in NumPy float64 in the tests on the CPU, and
  on the chip on one shot by ``scratch/lsm_probe.py``;
- **the reflectivity family**, made on the device from the seed:
  ``layers`` gently dipping interfaces (seeded depth, dip, amplitude,
  one pixel thick) plus ``N(0, noise)`` detail a pixel; **the data** is
  the builder's own plain modelling of it.

**Why "within tolerance of the reference" and not "of the true
reflectivity":** Kirchhoff LSM is ill-posed in a few iterations (a
band-limited wavelet, one-sided illumination from 8 shots): the few
iterations from zero the configuration runs recover the migrated
image's first corrections.

**What float32 determines of the answer, and what it does not**
(``loops/closed_vstack.py`` holds the program to the first and not to
the second): the operator's singular values spread widely (no
amplitude term: every pixel of an isochron counts alike, and a pixel
IN the acquisition surface shares its travel time with the whole
segment between source and receiver), float32 CGLS finds the largest
within five or six iterations and then cannot hold its orthogonality
to them, and from there ANY two float32 solves of one problem drift
apart tenfold an iteration — the plain reference against its own sums
in another order among them (``scratch/lsm_account.py``: the witnesses;
``sizes["z0"]`` lowers the image below the surface, which takes one
cause away and on the chip moves the onset from the fifth iteration to
the seventh; PERF.md section 6).

**Cost** (``chipbench/costs_lsm.py``): the tables once an iteration at
their stored 8 bytes plus the vector streams; 8 flops a pair-pixel.
"""

from __future__ import annotations

import inspect
import time
from types import SimpleNamespace

import numpy as np

# the deliberately wrong plain solve of the account (PERF.md section 6,
# PR 38): every sprayed and gathered product rounded to bfloat16, what a
# one-pass MXU contraction of m against a one-hot would give
CONTROLS = {"bf16": {"cast": "bfloat16"}}

PAIR_BLOCK = 8           # pairs a block of the plain spray and gather
BLOCK = (32, 32)         # the reference's own pixel order: a run of pixels
RUN = BLOCK[0] * BLOCK[1]

DEFAULT_SIZES = {"nz": 512, "nx": 1024, "dz": 4.0, "dx": 4.0, "nr": 256,
                 "dr": 16.0, "ns": 8, "dshot": 128.0, "nt": 1024,
                 "dt": 0.004, "vel": 2500.0, "f0": 20.0, "nwav": 81,
                 "layers": 6, "noise": 0.05}


def ricker(sizes: dict) -> np.ndarray:
    """``nwav`` samples of a Ricker wavelet of ``f0`` Hz, centred."""
    nw, f0, dt = int(sizes["nwav"]), float(sizes["f0"]), float(sizes["dt"])
    t = (np.arange(nw) - nw // 2) * dt
    a = (np.pi * f0 * t) ** 2
    return ((1.0 - 2.0 * a) * np.exp(-a)).astype(np.float32)


def geometry(sizes: dict) -> SimpleNamespace:
    """The survey: image axes from the surface down (from ``z0``
    metres under it where ``sizes`` has one: the account's experiment,
    no configuration's), receivers every ``dr`` and shots every
    ``dshot`` metres along the surface, the time axis, the wavelet.
    ``args``: what ``MPILSM`` takes, in its order."""
    nz, nx, nr, ns, nt = (int(sizes[k])
                          for k in ("nz", "nx", "nr", "ns", "nt"))
    z = float(sizes.get("z0", 0.0)) + np.arange(nz) * float(sizes["dz"])
    x = np.arange(nx) * float(sizes["dx"])
    t = np.arange(nt) * float(sizes["dt"])
    recs = np.vstack(((np.arange(nr) + 0.5) * float(sizes["dr"]),
                      np.zeros(nr)))
    sources = np.vstack(((np.arange(ns) + 0.5) * float(sizes["dshot"]),
                         np.zeros(ns)))
    wav = ricker(sizes)
    return SimpleNamespace(
        z=z, x=x, t=t, sources=sources, recs=recs, wav=wav,
        wavc=len(wav) // 2, vel=float(sizes["vel"]),
        args=(z, x, t, sources, recs, float(sizes["vel"]), wav,
              len(wav) // 2))


# --------------------------------------------------------- pixel order
def run_shape(sizes: dict) -> tuple:
    """The reference's own pixel order: runs of ``RUN`` pixels, each a
    block of the image (``BLOCK`` unless ``sizes["run"]`` gives another
    of ``RUN`` pixels: the same sums in another order,
    ``scratch/lsm_account.py``'s reordered witness)."""
    bz, bx = sizes.get("run", BLOCK)
    if bz * bx != RUN:
        raise ValueError(f"a run is {RUN} pixels")
    return int(bz), int(bx)


def padded(sizes: dict):
    (bz, bx), nz, nx = run_shape(sizes), int(sizes["nz"]), int(sizes["nx"])
    return -(-nz // bz) * bz, -(-nx // bx) * bx


def to_blocks(img, sizes: dict):
    """An image ``(nz, nx)`` in the reference's pixel order: its blocks
    one after another, zero-padded to whole blocks."""
    xp = np if isinstance(img, np.ndarray) else _jnp()
    (bz, bx), (pz, px) = run_shape(sizes), padded(sizes)
    v = xp.pad(img, ((0, pz - img.shape[0]), (0, px - img.shape[1])))
    return xp.swapaxes(v.reshape(pz // bz, bz, px // bx, bx), 1, 2).ravel()


def from_blocks(v, sizes: dict):
    """The way back: ``(npix_padded,)`` to the image ``(nz, nx)``."""
    (bz, bx), (pz, px) = run_shape(sizes), padded(sizes)
    img = _jnp().swapaxes(v.reshape(pz // bz, px // bx, bz, bx), 1, 2)
    return img.reshape(pz, px)[:int(sizes["nz"]), :int(sizes["nx"])]


def _jnp():
    import jax.numpy as jnp
    return jnp


# ------------------------------------------------------ plain operators
def point_times(sizes: dict, dtype=np.float32) -> dict:
    """The reference's OWN travel times, from the survey's geometry
    alone: straight rays in the constant velocity from every source
    (``ts (ns, nruns, RUN)``) and every receiver (``tr (nr, nruns,
    RUN)``) to every pixel, in seconds, made on the device in
    ``dtype``; the pixels in the reference's own order (``to_blocks``:
    runs of ``RUN``), ``inside (nruns, RUN)`` false on the padding of
    the last blocks; ``dt`` rides along as a device scalar. Point-sized
    ((ns + nr) x npix: 0.55 GB at the configuration's sizes); the
    pair-sized tables are derived from it a block of pairs at a time
    (:func:`pair_tables`) and never stored."""
    import jax
    import jax.numpy as jnp
    geo = geometry(sizes)
    zz, xx = np.meshgrid(geo.z, geo.x, indexing="ij")
    px = to_blocks(xx, sizes).reshape(-1, RUN)
    pz = to_blocks(zz, sizes).reshape(-1, RUN)
    inside = to_blocks(np.ones(zz.shape, bool), sizes).reshape(-1, RUN)

    @jax.jit
    def times(points, px, pz, vel):                      # points (2, n)
        dx = points[0][:, None, None] - px
        dz = points[1][:, None, None] - pz
        return jnp.sqrt(dx * dx + dz * dz) / vel
    as_ = lambda a: jnp.asarray(a, dtype)                # noqa: E731
    px, pz, vel = as_(px), as_(pz), as_(geo.vel)
    return {"ts": times(as_(geo.sources), px, pz, vel),
            "tr": times(as_(geo.recs), px, pz, vel),
            "inside": jnp.asarray(inside), "dt": as_(sizes["dt"])}


def pair_blocks(times: dict) -> tuple:
    """``(nblocks, pb)``: the pairs (source-major, as the data lies)
    cut into blocks of ``PAIR_BLOCK`` receivers of one source (of one
    where the receivers are no multiple of it)."""
    ns, nr = times["ts"].shape[0], times["tr"].shape[0]
    pb = PAIR_BLOCK if nr % PAIR_BLOCK == 0 else 1
    return ns * nr // pb, pb


def pair_tables(times: dict, b, nt: int):
    """The equations' ``(i, tau)`` of block ``b``'s pairs, ``(pb,
    nruns, RUN)``: ``T = (t_s + t_r) / dt``, ``i = floor(T)``, ``tau =
    T - i``; where ``0 <= i < nt - 1`` does not hold, or the pixel is
    padding, the entry is dropped (``i`` = -1, ``tau`` = 0)."""
    import jax
    import jax.numpy as jnp
    _, pb = pair_blocks(times)
    nr = times["tr"].shape[0]
    first = b * pb
    T = (jax.lax.dynamic_index_in_dim(times["ts"], first // nr, 0)
         + jax.lax.dynamic_slice_in_dim(times["tr"], first % nr, pb, 0)) \
        / times["dt"]
    i = jnp.floor(T)
    keep = times["inside"] & (i >= 0) & (i < nt - 1)
    return (jnp.where(keep, i, -1).astype(jnp.int32),
            jnp.where(keep, T - i, 0))


def _rounding(cast):
    """``f(v)``: ``v`` rounded to ``cast`` and back (``None``: as it
    is). By ``lax.reduce_precision``, which is an instruction of its
    own: written as ``astype`` there and back the chip's compiler
    computes the round trip in excess precision and the control reads
    0.0 from the reference (my chip run, PR 38)."""
    if cast is None:
        return lambda v: v
    import jax
    import jax.numpy as jnp
    info = jnp.finfo(cast)
    return lambda v: jax.lax.reduce_precision(v, info.nexp, info.nmant)


def plain_spray(sizes: dict, cast=None):
    """``(mv, rmv)`` of the indexed part alone: ``mv(times, m)`` sprays
    the pixels ``m (npix_padded,)`` (in the reference's order) to
    ``(pairs, nt)``; ``rmv(times, z)`` gathers ``z (pairs, nt)`` back.
    The equations as they stand, a block of pairs at a time
    (:func:`pair_tables`): ``.at[].add`` of the two taps, the gather by
    indexing; a dropped entry lands beyond the trace's end.

    ``cast="bfloat16"`` (the control): every product ``(1 - tau) m``,
    ``tau m``, ``(1 - tau) z[i]``, ``tau z[i + 1]`` rounded to bfloat16
    before it is summed."""
    import jax
    import jax.numpy as jnp
    nt = int(sizes["nt"])

    rounded = _rounding(cast)

    def mv(times, m):
        nblocks, pb = pair_blocks(times)
        m = m.reshape(times["inside"].shape)
        rows = jnp.arange(pb)[:, None, None]

        def block(b):
            i, w = pair_tables(times, b, nt)
            at = jnp.where(i >= 0, i, nt)
            y = jnp.zeros((pb, nt + 2), m.dtype)
            y = y.at[rows, at].add(rounded((1 - w) * m))
            y = y.at[rows, at + 1].add(rounded(w * m))
            return y[:, :nt]
        return jax.lax.map(block, jnp.arange(nblocks)).reshape(-1, nt)

    def rmv(times, z):
        nblocks, pb = pair_blocks(times)
        zb = jnp.pad(z, ((0, 0), (0, 2))).reshape(nblocks, pb, nt + 2)
        rows = jnp.arange(pb)[:, None, None]

        def block(acc, row):
            b, zp = row
            i, w = pair_tables(times, b, nt)
            at = jnp.where(i >= 0, i, nt)
            g = rounded((1 - w) * zp[rows, at]) \
                + rounded(w * zp[rows, at + 1])
            return acc + jnp.sum(g, axis=0), None
        acc, _ = jax.lax.scan(
            block, jnp.zeros(times["inside"].shape, z.dtype),
            (jnp.arange(nblocks), zb))
        return acc.ravel()

    return mv, rmv


def band_width(sizes: dict, times: dict) -> int:
    """The ``width`` :func:`banded_spray` needs: the longest run of
    samples a run of ``RUN`` pixels spans for one pair (``max - min +
    1`` over its kept indices, over every pair) and the second tap's
    sample, in whole eights."""
    import jax
    import jax.numpy as jnp
    nt = int(sizes["nt"])

    @jax.jit
    def longest(times):
        def block(b):
            i, _ = pair_tables(times, b, nt)
            hi = jnp.max(jnp.where(i >= 0, i, -1), axis=-1)
            lo = jnp.min(jnp.where(i >= 0, i, 1 << 30), axis=-1)
            return jnp.max(hi - lo)
        return jnp.max(jax.lax.map(block,
                                   jnp.arange(pair_blocks(times)[0]))) + 1
    return 8 * (-(-(max(int(longest(times)), 1) + 1) // 8))


def banded_spray(sizes: dict, width: int, cast=None):
    """``(mv, rmv)`` as :func:`plain_spray`, by compares over a short
    band (module docstring): for each run of ``RUN`` pixels of a pair,
    ``lo`` its smallest kept index, the spray is ``strip[w] = sum_x
    [i[x] - lo == w] (1 - tau) m[x] + [i[x] - lo == w - 1] tau m[x]``
    for ``w < width``, added to the trace at ``lo + w``; the gather
    reads the strip ``z[lo + w]`` and selects. ``width`` must hold
    every run's band and the second tap (:func:`band_width`)."""
    import jax
    import jax.numpy as jnp
    nt, W = int(sizes["nt"]), int(width)

    rounded = _rounding(cast)

    def relative(i):
        """``lo (pb, nruns)`` and ``i - lo`` with a band axis before
        the run's own: ``(pb, nruns, 1, RUN)``."""
        lo = jnp.min(jnp.where(i >= 0, i, nt), axis=-1)
        return lo, (i - lo[..., None])[:, :, None]

    band = jnp.arange(W).reshape(W, 1)

    def mv(times, m):
        nblocks, pb = pair_blocks(times)
        m = m.reshape(times["inside"].shape)
        rows = jnp.arange(pb)[:, None, None]

        def block(b):
            i, w = pair_tables(times, b, nt)
            lo, rel = relative(i)
            first = rounded((1 - w) * m)[:, :, None]
            second = rounded(w * m)[:, :, None]
            strip = jnp.sum(jnp.where(rel == band, first, 0)
                            + jnp.where(rel == band - 1, second, 0),
                            axis=-1)                      # (pb, nruns, W)
            at = lo[..., None] + jnp.arange(W)
            y = jnp.zeros((pb, nt + W), m.dtype)
            return y.at[rows, at].add(strip)[:, :nt]
        return jax.lax.map(block, jnp.arange(nblocks)).reshape(-1, nt)

    def rmv(times, z):
        nblocks, pb = pair_blocks(times)
        zb = jnp.pad(z, ((0, 0), (0, W + 1))).reshape(nblocks, pb,
                                                      nt + W + 1)
        rows = jnp.arange(pb)[:, None, None]

        def block(acc, row):
            b, zp = row
            i, w = pair_tables(times, b, nt)
            lo, rel = relative(i)
            strip = zp[rows, lo[..., None] + jnp.arange(W + 1)]
            here = rel == band
            z0 = jnp.sum(jnp.where(here, strip[..., :W, None], 0), axis=2)
            z1 = jnp.sum(jnp.where(here, strip[..., 1:, None], 0), axis=2)
            g = rounded((1 - w) * z0) + rounded(w * z1)
            return acc + jnp.sum(g, axis=0), None
        acc, _ = jax.lax.scan(
            block, jnp.zeros(times["inside"].shape, z.dtype),
            (jnp.arange(nblocks), zb))
        return acc.ravel()

    return mv, rmv


def plain_wavelet(sizes: dict):
    """``(conv, corr)`` along time on ``(pairs, nt)``: ``d[t] = sum_j
    w[j] y[t + c - j]`` as ``nwav`` shifted sums, and its adjoint
    ``z[t] = sum_j w[j] d[t - c + j]``, zeros beyond the trace."""
    import jax.numpy as jnp
    w = ricker(sizes)
    nw, c, nt = len(w), len(w) // 2, int(sizes["nt"])

    def shifted(v, flip: bool):
        # out[t] = sum_j w[j] v[t + s_j]: s_j = c - j, or j - c flipped
        vp = jnp.pad(v, ((0, 0), (nw, nw)))
        out = jnp.zeros_like(v)
        for j in range(nw):
            s = (j - c) if flip else (c - j)
            out = out + w[j] * vp[:, nw + s:nw + s + nt]
        return out
    return (lambda y: shifted(y, False)), (lambda d: shifted(d, True))


def plain_system(sizes: dict, cast=None, width=None):
    """``(mv, rmv)`` of the chip's share of the demigration on flat
    vectors, each taking the reference's travel times
    (:func:`point_times`) first: image ``(nz * nx,)`` to data ``(pairs
    * nt,)`` and back. ``width``: the indexed part by
    :func:`banded_spray` of that width; ``None``: by
    :func:`plain_spray`."""
    spray, gather = plain_spray(sizes, cast) if width is None \
        else banded_spray(sizes, width, cast)
    conv, corr = plain_wavelet(sizes)
    nz, nx, nt = (int(sizes[k]) for k in ("nz", "nx", "nt"))

    def mv(times, m):
        return conv(spray(times, to_blocks(m.reshape(nz, nx),
                                           sizes))).ravel()

    def rmv(times, d):
        return from_blocks(gather(times, corr(d.reshape(-1, nt))),
                           sizes).ravel()
    return mv, rmv


def plain_solve(sizes: dict, niter: int, cast=None, width=None):
    """``f(times, d) -> (x, drop)``: textbook CGLS from zero on the
    plain system for the flat data ``d``, and the residual's norm after
    ``niter`` iterations over the data's (its first). The residual is
    made as an array by one program and its norm taken by another, as
    ``builders/mdd.py::plain_solve`` does and for its reason."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference

    mv, rmv = plain_system(sizes, cast, width)

    @jax.jit
    def solve(times, d):
        with jax.default_matmul_precision("highest"):
            return reference.cgls(lambda c: mv(times, c),
                                  lambda s: rmv(times, s),
                                  lambda u: jnp.sum(u * u), d, niter)

    @jax.jit
    def residual(times, d, x):
        with jax.default_matmul_precision("highest"):
            return d - mv(times, x)

    share = jax.jit(lambda r, d: jnp.sqrt(jnp.sum(r * r) / jnp.sum(d * d)))

    def drop(times, d, x):
        return share(residual(times, d, x), d)

    def both(times, d):
        x = solve(times, d)
        return x, drop(times, d, x)

    both.solve, both.drop = solve, drop
    return both


# ----------------------------------------------------------- the family
def make_reflectivity(sizes: dict):
    """``f(key) -> m (nz * nx,)``: ``layers`` dipping interfaces one
    pixel thick (seeded depth in the middle 80 % of the image, dip up
    to 10 %, amplitude N(0, 1)) plus ``N(0, noise)`` detail."""
    import jax
    import jax.numpy as jnp
    nz, nx = int(sizes["nz"]), int(sizes["nx"])
    L, noise = int(sizes["layers"]), float(sizes["noise"])

    def reflectivity(key):
        kd, ks, ka, kn = jax.random.split(key, 4)
        depth = jax.random.uniform(kd, (L,), jnp.float32, 0.1, 0.9) * nz
        dip = jax.random.uniform(ks, (L,), jnp.float32, -0.1, 0.1)
        amp = jax.random.normal(ka, (L,), jnp.float32)
        zi = jnp.arange(nz, dtype=jnp.float32)[None, :, None]
        xi = jnp.arange(nx, dtype=jnp.float32)[None, None, :]
        at = depth[:, None, None] + dip[:, None, None] * (xi - nx / 2)
        m = jnp.sum(amp[:, None, None] * jnp.exp(-(zi - at) ** 2), axis=0)
        return (m + noise * jax.random.normal(kn, (nz, nx),
                                              jnp.float32)).ravel()
    return jax.jit(reflectivity)


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import pylops_mpi_tpu as pmt
    from chipbench import costs_lsm

    nz, nx, nr, ns, nt = (int(sizes[k])
                          for k in ("nz", "nx", "nr", "ns", "nt"))
    pairs, npix = ns * nr, nz * nx
    if pairs * nt == npix:
        raise ValueError("data and model of one length: dep.vector tells "
                         "them apart by it")
    geo = geometry(sizes)
    if "frac" not in inspect.signature(
            pmt.models.TravelTimeSpray).parameters:
        # a program from before PR 38: its MPILSM is another operator
        # (one rounded tap, an amplitude) and builds five float64
        # (pairs, npix) arrays on the HOST — 43 GB at these sizes. Fail
        # at once, before anything is allocated.
        raise SystemExit("chipbench: this checkout's pmt.models.MPILSM is "
                         "not the two-tap operator with device-made tables "
                         "(no TravelTimeSpray(frac=)); lsm_kirchhoff cannot "
                         "run on it")

    t0 = time.perf_counter()
    # upstream's arguments and nothing else; the tables are made on the
    # device inside (the deployment's only large state)
    Op = pmt.models.MPILSM(*geo.args, mesh=mesh)
    held = jax.block_until_ready(jax.tree_util.tree_leaves(Op))
    table_bytes = sum(int(a.nbytes) for a in held)
    construct_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    # the reference's own travel times, from the geometry alone:
    # nothing of the program's tables or their layout is read
    times = jax.block_until_ready(point_times(sizes))
    width = band_width(sizes, times)
    times_s = time.perf_counter() - t0
    log(f"program: {table_bytes} bytes held by the operator; reference: "
        f"per-point travel times {times['ts'].nbytes + times['tr'].nbytes} "
        f"bytes, band {width} samples a run of {RUN} pixels")

    reflectivity = make_reflectivity(sizes)
    mv, _ = plain_system(sizes, width=width)
    model = jax.jit(mv)

    def rhs(j: int, seed_: int):
        """Pool member ``j`` of the seed: the data ``d``, flat, a device
        array: the plain modelling of a seeded reflectivity."""
        k = jax.random.fold_in(jax.random.key(int(seed_)), 1 + j)
        with jax.default_matmul_precision("highest"):
            return model(times, reflectivity(k))

    solves = {}

    def plain(niter_: int, kind=None):
        """The plain solve of that depth (``kind``: a control's), made
        once."""
        key = (int(niter_), kind)
        if key not in solves:
            solves[key] = plain_solve(sizes, int(niter_), width=width,
                                      **(CONTROLS[kind] if kind else {}))
        return solves[key]

    def reference(d, niter_: int) -> SimpleNamespace:
        """The plain solve of a pool member after ``niter_`` iterations:
        the answer ``x`` and the residual's ``drop``."""
        x, drop = plain(niter_)(times, d)
        return SimpleNamespace(x=x, drop=drop)

    def drop(d, x):
        """The residual of an answer ``x`` for the data ``d`` over the
        data's norm, by the plain forward in a program of its own."""
        return plain(cfg["guarantees"]["niter"]).drop(times, d, x)

    def vector(n: int, a=None):
        """The vectors upstream's tutorial makes: the data
        ``Partition.SCATTER`` (over shots), the model
        ``Partition.BROADCAST``; told apart by their length. Holds
        ``a`` (zeros when not given)."""
        part = pmt.Partition.SCATTER if n == pairs * nt \
            else pmt.Partition.BROADCAST
        out = pmt.DistributedArray(global_shape=n, mesh=mesh,
                                   partition=part, dtype=np.float32)
        if a is not None:
            out[:] = a
        return out

    def control(kind: str):
        """A deliberately wrong plain solve (``CONTROLS``) in the form
        the loop calls the program in — ``f(y, x0, niter) -> x`` on the
        program's vectors — for ``dep.stand_in``."""
        def solve(y, x0, niter_):
            return vector(npix, plain(niter_, kind).solve(times, y.array))
        return solve

    return SimpleNamespace(
        op=Op, mesh=mesh, nrows=pairs * nt, ncols=npix,
        rhs=rhs, reference=reference, drop=drop, vector=vector,
        control=control, stand_in=None, times=times, width=width,
        cost=lambda k=1: costs_lsm.iteration(sizes),
        kirchhoff_cost=lambda: costs_lsm.kirchhoff(sizes),
        dtype="float32", resid_ratio=float(cfg["guarantees"]["resid_ratio"]),
        repeat_tol=float(cfg["guarantees"]["repeat_tol"]),
        split={"construct_s": construct_s, "reference_times_s": times_s},
        describe=f"image {nz}x{nx} ({4 * npix} bytes) BROADCAST, {ns} "
                 f"shots x {nr} receivers = {pairs} pairs, data "
                 f"{pairs}x{nt} float32 SCATTER ({4 * pairs * nt} bytes), "
                 f"tables {pairs}x{npix} x 8 B ({table_bytes} bytes held), "
                 f"dt {sizes['dt']} s, "
                 f"{sizes['vel']} m/s, Ricker {sizes['f0']} Hz of "
                 f"{sizes['nwav']}, {type(Op).__name__} on "
                 f"{int(mesh.devices.size)} device(s)")
