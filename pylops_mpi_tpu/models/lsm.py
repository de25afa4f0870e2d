"""Least-squares (Kirchhoff) migration.

Application-layer analog of the reference's ``tutorials/lsm.py``: there
each rank builds a ``pylops.waveeqprocessing.LSM`` (Kirchhoff
demigration for its batch of sources) and the ranks are stacked with
``MPIVStack`` — model BROADCAST, data SCATTER over sources, adjoint
sum-allreduce (ref ``pylops_mpi/basicoperators/VStack.py:135-150``).

**What is computed** is PyLops' static Kirchhoff operator, as ``LSM``
builds it by default: for a source-receiver pair ``p = (s, r)`` and a
pixel ``x``, ``T = (t_s(x) + t_r(x)) / dt``, ``i = floor(T)``, ``tau =
T - i``, and where ``0 <= i < nt - 1``

    spray:   y[p, i] += (1 - tau) m[x];  y[p, i + 1] += tau m[x];  d = w * y
    adjoint: m[x] = sum_p (1 - tau) z[p, i] + tau z[p, i + 1],  z = w (*) d

two weighted taps a pixel (linear interpolation between samples), no
amplitude term, straight-ray travel times in a constant velocity
(``mode="analytic"``), the wavelet a :class:`~pylops_mpi_tpu.ops.local.
Conv1D` along time.

**How.** The per-pair tables ``(i, tau)`` — int32 and the operator's
dtype, 8 bytes a pair-pixel in float32 — are the operator's memory and
are STORED (as PyLops 2.0 did), made ON the device, in their stored
dtypes, from the per-point travel times ``(ns + nr, npix)`` in one
program: nothing pair-sized exists on the host. Both applies are one
Pallas kernel each, ``pmt_kirchhoff`` / ``pmt_kirchhoff_adj``
(``ops/pallas_kernels.py``: the index becomes compares of a tile of
1,024 pixels against the samples of the band its travel times span —
in the adjoint, for a band that fits a 128-sample window of the trace,
two lane gathers from that window; compiled on a TPU, interpreted
elsewhere — one form on every backend).
So that a tile's band is short the tables hold the image's pixels in
blocks of 32 x 32 (:class:`_BlockOrder` puts a model in that order, a
2 MB transpose an apply). The tables are pytree children of the
operator (``register_operator_arrays``): the fused solvers take them
as ``jit`` arguments, never as constants of the compiled program.

**On several devices** ``MPILSM`` makes shard ``c``'s tables on mesh
device ``c`` (``KirchhoffDemigration(device=)``): no table crosses a
chip or the host. The blocks are alike, so ``MPIVStack`` takes its
sharded form (``ops/stack.py``): the tables of all shards are laid into
one stack sharded over the mesh with no copy, each device applies its
own block under ``shard_map``, and the adjoint's partial images meet in
one ``psum`` of the image (``pmt.collective.stack_reduce``). A shot
count that is no multiple of the devices gives blocks of two sizes: the
replicated form, every table on the default device as before.

**Departures from upstream that remain**: travel times are analytic
only (no eikonal, no user-supplied tables through ``MPILSM``; a
:class:`TravelTimeSpray` takes any tables); ``dynamic=True`` (amplitude
and obliquity weights) is not there.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace
from ..distributedarray import DistributedArray, Partition
from ..linearoperator import register_operator_arrays
from ..ops import pallas_kernels as _pk
from ..ops.blockdiag import MPIBlockDiag  # noqa: F401  (re-export convenience)
from ..ops.stack import MPIVStack
from ..ops.local import Conv1D, LocalOperator, _scoped
from ..solvers.basic import cgls

__all__ = ["TravelTimeSpray", "KirchhoffDemigration", "MPILSM", "lsm"]

_TILE = _pk.KIRCHHOFF_TILE
_BLOCK = (32, 32)        # pixels of the image a tile of the tables holds


@partial(jax.jit, static_argnames=("last", "taps"))
def _pack(i, w, inside, last: int, taps: int = 2):
    """Tables ``(pairs, npix)`` of ``taps`` taps in the kernels' layout,
    the longest band, the count of entries dropped (``i`` outside ``[0,
    last]`` at a pixel that is ``inside`` the image) and the counts of
    pair-tiles ``(not empty, read by lane gather)`` in the adjoint
    (``pallas_kernels.kirchhoff_windowed``) and of the spray's loop
    steps ``(walked, of the pairs' own bands)``: its groups of
    ``pallas_kernels.kirchhoff_group`` traces walk each tile's union
    band once a trace."""
    inrange = (i >= 0) & (i <= last)
    it, wt, lohi = _pk.kirchhoff_pack(i, w, inside & inrange)
    lo, hi = lohi[:, :, 0], lohi[:, :, 1]
    band = jnp.max(hi - lo) + 1
    pairs = lo.shape[0]
    g = _pk.kirchhoff_group(pairs, last + taps, w.dtype)
    union = (jnp.min(lo.reshape(pairs // g, g, *lo.shape[1:]), axis=1),
             jnp.max(hi.reshape(pairs // g, g, *hi.shape[1:]), axis=1))
    tiles = jnp.stack([jnp.sum(lo <= hi),
                       jnp.sum(_pk.kirchhoff_windowed(lo, hi, taps)),
                       g * jnp.sum(_pk._kir_steps(union[0],
                                                  union[1] + taps - 1)),
                       jnp.sum(_pk._kir_steps(lo, hi + taps - 1))])
    return it, wt, lohi, band, jnp.sum(inside & ~inrange), tiles


class TravelTimeSpray(LocalOperator):
    """Spray image points onto the samples of source-receiver traces
    through stored tables ``(npairs, npix)``; the adjoint gathers.

    - ``TravelTimeSpray(itrav, amp, nt)``: ONE weighted tap, ``y[p,
      itrav[p, x]] += amp[p, x] m[x]`` where ``0 <= itrav < nt``;
    - ``TravelTimeSpray(itrav, None, nt, frac=tau)``: TWO taps,
      ``y[p, i] += (1 - tau) m[x]; y[p, i + 1] += tau m[x]`` where
      ``0 <= i < nt - 1`` — PyLops' static Kirchhoff.

    Other entries are dropped. One form on every backend, chosen by
    what the operator sees: the Pallas kernels ``pmt_kirchhoff`` /
    ``pmt_kirchhoff_adj`` for real data whose spray accumulator fits
    VMEM (``pallas_kernels.kirchhoff_legal``: 6,144 samples of
    float32), else (``why``: ``dtype`` or ``nt``) a trace-by-trace
    scatter-add / gather. Pixels are taken in the order given, 1,024 a
    tile; a tile costs a few vector operations a sample of the BAND its
    indices span, so neighbours in the table should be neighbours in
    time. The spray takes ``G`` consecutive traces a grid step
    (``pallas_kernels.kirchhoff_group``, a rule in ``npairs``, ``nt``
    and the dtype: 4 in the ``lsm_kirchhoff`` cell), a tile walking the
    union of their bands, so neighbouring traces should be neighbours
    in time too (shot-major pairs are). The adjoint reads a tile whose
    band fits a 128-sample window
    of the trace (up to 65 - taps samples: every tile of the
    ``lsm_kirchhoff`` cell) by two lane gathers, whatever its length,
    and walks any other. ``kirchhoff.path_select`` (``form``,
    ``pairs``, ``npix``, ``nt``, ``tile``, ``band``, ``adjoint``,
    ``why``; a forward's kernel also ``group``: ``G``, and ``walk``:
    the samples its union bands walk over those of the pairs' own
    bands; an adjoint's kernel ``windowed``: the share of the
    non-empty pair-tiles read by lane gather) says what a traced apply
    took; the counters ``kirchhoff.pair_pixels`` and
    ``kirchhoff.taps_dropped`` count the tables' entries at
    construction, ``kirchhoff.gather_tiles_windowed`` the pair-tiles
    the adjoint reads by lane gather, ``kirchhoff.spray_group`` the
    spray's ``G``. Measured on a TPU v5e: see :func:`_form`."""

    # the blocks of one stack differ in these counts of their tables
    # alone; a sharded stack reports the longest band and the totals
    shard_merge = {"band": max, "dropped": sum, "tiles": sum,
                   "windowed": sum, "steps_walked": sum, "steps_banded": sum}

    @property
    def whole(self):
        """Off a TPU the kernels are interpreted and their loops run
        over each tile's band, a count read from the tables: the
        partitioner must not cut them (``LocalOperator.whole``)."""
        return _pk._interpret()

    def __init__(self, itrav, amp, nt: int, dtype=np.float32, frac=None):
        if (amp is None) == (frac is None):
            raise ValueError("TravelTimeSpray: give amp (one tap) or "
                             "frac (two taps), not both")
        npairs, npix = itrav.shape
        pad = -npix % _TILE
        widths = ((0, 0), (0, pad))
        w = jnp.pad(jnp.asarray(amp if frac is None else frac, dtype=dtype),
                    widths)
        self._init_packed(
            _pack(jnp.pad(jnp.asarray(itrav, dtype=jnp.int32), widths), w,
                  jnp.arange(npix + pad) < npix,
                  last=int(nt) - (1 if frac is None else 2),
                  taps=1 if frac is None else 2),
            npairs, npix, nt, 1 if frac is None else 2, dtype)

    @classmethod
    def _from_packed(cls, packed, npairs, npix, nt, taps, dtype):
        self = cls.__new__(cls)
        self._init_packed(packed, npairs, npix, nt, taps, dtype)
        return self

    def _init_packed(self, packed, npairs, npix, nt, taps, dtype):
        # a compile for a described chip packs abstract tables: no counts
        self._it, self._wt, self._lohi, band, dropped = packed[:5]
        self.nt, self.taps = int(nt), int(taps)
        self.band = int(band) + self.taps - 1
        self.dropped = int(dropped)
        self.tiles, self.windowed, self.steps_walked, self.steps_banded = (
            (int(n) for n in np.asarray(packed[5])) if len(packed) > 5
            else (0, 0, 0, 0))
        self.group = _pk.kirchhoff_group(npairs, self.nt, dtype)
        _metrics.inc("kirchhoff.pair_pixels", npairs * npix)
        _metrics.inc("kirchhoff.taps_dropped", self.dropped)
        _metrics.inc("kirchhoff.gather_tiles_windowed", self.windowed)
        _metrics.inc("kirchhoff.spray_group", self.group)
        LocalOperator.__init__(self, dims=npix, dimsd=(npairs, self.nt),
                               dtype=dtype)

    @property
    def itrav(self) -> jax.Array:
        """The stored indices as they lie, ``(npairs, ntiles, 8, 128)``
        int32: pixel ``x`` at flat place ``x`` of its pair's row, the
        pixels padded to whole tiles (and whole grid steps of the
        kernels); a dropped entry is negative."""
        return self._it

    @property
    def weight(self) -> jax.Array:
        """The stored weights (``amp``, or ``frac`` for two taps), laid
        as :attr:`itrav`; zero where the entry is dropped."""
        return self._wt

    @property
    def table_bytes(self) -> int:
        return sum(int(a.nbytes) for a in (self._it, self._wt, self._lohi))

    def _form(self, dtype, adjoint: bool):
        """``None`` where the kernels take the apply, else the one word
        ``kirchhoff.path_select`` gives as ``why``; the event is
        recorded here.

        Measured on a TPU v5e (``chipbench/scratch/lsm_probe.py``,
        PR 38; ms an apply, forward / adjoint, one shot of the
        ``lsm_kirchhoff`` cell: 256 pairs, 524,288 pixels in 32 x 32
        blocks, 1,024 samples, two taps, float32, tables 1.07 GB):

        ==================================  ========  ========
        form                                forward   adjoint
        ==================================  ========  ========
        ``pmt_kirchhoff`` / ``_adj``        9.82      3.46
        (``pmt_kirchhoff`` a trace a step   20.2
        ``_adj`` walking every band,                  13.0)
        scatter-add / gather, a trace       2,360     2,137
        (the benchmark's plain forms:
        scatter and index, 8 pairs a block  2,348     2,320
        one-hot over a band of 40, jnp      100       70)
        ==================================  ========  ========

        (bands of 26 samples a tile on average, 38 the longest; the
        kernels agree with the plain scatter to 9.9e-7 forward and
        3.0e-7 adjoint, float32 sums in another order.) The spray takes
        4 traces a grid step (``chip_probe/kirchhoff_spray_probe.py``,
        that shot: one trace
        a step 20.23 ms, the same with a step's loads before its stores
        16.85, 2 traces 12.07, 4 9.82; at 8 shots 160.16, 133.63, 93.42
        and **75.00**, ``walk`` 1.04 at 2 and 1.11 at 4; 8 traces do not
        fit VMEM at 1,024 samples, and at 512 read 51.02 against 4's
        56.74 and one trace's 126.88; equal to one trace a step bit for
        bit). The adjoint
        reads every tile of that shot by lane gather since PR 39
        (``chip_probe/kirchhoff_gather_probe.py``: 16 tiles a step of
        its loop 3.46 ms, 8 3.93, 4 5.12, a branch a tile 14.76, the
        band loop 12.98; at 8 shots 21.9 against 102.1, 20.2 in the
        cell's loop, 99.1 before; its window by a stride-0 broadcast
        load 18.2, not taken: the interpreter has no such load; equal
        to the band loop bit for bit). XLA's scatter
        and gather cost 8.7 ns an entry on the chip, the kernels 0.08
        and 0.05: the scatter form is for what the kernels cannot take
        (complex data, a trace whose accumulator outgrows VMEM), never
        for speed.

        A rule in what the operator sees, the same on every backend, so
        the CPU tests run the form the chip runs."""
        why = None
        if jnp.issubdtype(dtype, jnp.complexfloating) \
                or np.dtype(self.dtype).kind != "f":
            why = "dtype"
        elif not _pk.kirchhoff_legal(self.nt, dtype):
            why = "nt"
        extra = {"why": why} if why else {}
        if adjoint and not why:
            extra["windowed"] = self.windowed / max(self.tiles, 1)
        elif not why:
            extra["group"] = _pk.kirchhoff_group(self.dimsd[0], self.nt,
                                                 dtype)
            extra["walk"] = self.steps_walked / max(self.steps_banded, 1)
        _trace.event("kirchhoff.path_select", cat="schedule",
                     form="scatter" if why else "pmt_kirchhoff",
                     pairs=self.dimsd[0], npix=self.dims[0], nt=self.nt,
                     tile=_TILE, band=self.band, adjoint=int(adjoint),
                     **extra)
        return why

    def _flat_tables(self):
        npairs = self.dimsd[0]
        return (self._it.reshape(npairs, -1), self._wt.reshape(npairs, -1))

    @_scoped
    def _matvec(self, x):
        npairs, nt = self.dimsd
        npad = self._it.shape[1] * _TILE
        m = jnp.pad(x.astype(jnp.result_type(x.dtype, self.dtype)),
                    (0, npad - x.shape[0]))
        if self._form(m.dtype, adjoint=False) is None:
            return _pk.kirchhoff_spray(self._lohi, self._it, self._wt, m,
                                       nt, self.taps).ravel()
        it, wt = self._flat_tables()

        def trace_of(tables):
            i, w = tables                 # a dropped index is out of range
            y = jnp.zeros(nt + 1, m.dtype)
            if self.taps == 1:
                return y.at[i].add(w * m, mode="drop")[:nt]
            return y.at[i].add((1 - w) * m, mode="drop") \
                    .at[i + 1].add(w * m, mode="drop")[:nt]
        return lax.map(trace_of, (it, wt)).ravel()

    @_scoped
    def _rmatvec(self, x):
        npairs, nt = self.dimsd
        z = x.reshape(npairs, nt)
        z = z.astype(jnp.result_type(z.dtype, self.dtype))
        if self._form(z.dtype, adjoint=True) is None:
            m = _pk.kirchhoff_gather(self._lohi, self._it, self._wt, z,
                                     self.taps)
            return m[:self.dims[0]]
        it, wt = self._flat_tables()
        wt = jnp.conj(wt)

        def add_trace(acc, row):
            zp, i, w = row
            live = i >= 0
            zp = jnp.pad(zp, (0, 1))
            g0 = jnp.where(live, zp[jnp.clip(i, 0, nt)], 0)
            if self.taps == 1:
                return acc + w * g0, None
            g1 = jnp.where(live, zp[jnp.clip(i + 1, 0, nt)], 0)
            return acc + ((1 - w) * g0 + w * g1), None
        acc, _ = lax.scan(add_trace, jnp.zeros(it.shape[1], z.dtype),
                          (z, it, wt))
        return acc[:self.dims[0]]


class _BlockOrder(LocalOperator):
    """An image ``(nz, nx)`` as its blocks of ``_BLOCK`` pixels one
    after another, zero-padded to whole blocks: the order in which
    :func:`KirchhoffDemigration` keeps its tables, so that the 1,024
    pixels of a tile are neighbours in the image and their travel
    times neighbours in time. A permutation (and a pad): the adjoint is
    the way back."""

    def __init__(self, dims, dtype=np.float32):
        (nz, nx), (bz, bx) = dims, _BLOCK
        self.padded = (-(-nz // bz) * bz, -(-nx // bx) * bx)
        super().__init__(dims, int(np.prod(self.padded)), dtype=dtype)

    def order(self, image):
        """``image (nz, nx, ...)`` -> ``(npix_padded, ...)``; NumPy or
        JAX."""
        (nz, nx), (pz, px), (bz, bx) = self.dims, self.padded, _BLOCK
        xp = jnp if isinstance(image, jax.Array) else np
        rest = image.shape[2:]
        v = xp.pad(image, ((0, pz - nz), (0, px - nx))
                   + ((0, 0),) * len(rest))
        v = v.reshape((pz // bz, bz, px // bx, bx) + rest)
        return xp.swapaxes(v, 1, 2).reshape((pz * px,) + rest)

    def _matvec(self, x):
        return self.order(x.reshape(self.dims))

    def _rmatvec(self, x):
        (nz, nx), (pz, px), (bz, bx) = self.dims, self.padded, _BLOCK
        v = x.reshape(pz // bz, px // bx, bz, bx)
        return jnp.swapaxes(v, 1, 2).reshape(pz, px)[:nz, :nx].ravel()


@partial(jax.jit, static_argnames=("nt",))
def _tables(srcs, rcvs, pix, inside, vel, dt, nt: int):
    """The packed per-pair tables of a batch of sources, made where
    they are to lie: per-point straight-ray travel times ``(ns, npix)``
    and ``(nr, npix)``, their sums over ``dt`` a pair, ``floor`` and
    fraction — each table written once, in its stored dtype."""
    def times(points):                                   # (n, npix)
        d = points[:, None, :] - pix[None, :, :]
        return jnp.sqrt(jnp.sum(d * d, axis=-1)) / vel
    T = (times(srcs)[:, None, :] + times(rcvs)[None, :, :]) / dt
    T = T.reshape(-1, pix.shape[0])
    i = jnp.floor(T)
    return _pack(i.astype(jnp.int32), T - i, inside, last=nt - 2)


def KirchhoffDemigration(z: np.ndarray, x: np.ndarray, t: np.ndarray,
                         sources: np.ndarray, recs: np.ndarray, vel: float,
                         wav: np.ndarray, wavcenter: int,
                         dtype=np.float32, device=None) -> LocalOperator:
    """Kirchhoff demigration ``d(s, r, t) = w(t) * sum_x m(x)
    hat(t - t_s(x) - t_r(x))`` for one batch of sources (module
    docstring: two taps a pixel, no amplitude, constant-velocity
    straight rays; the engine inside ``pylops.waveeqprocessing.LSM``
    the reference stacks, ref ``tutorials/lsm.py``): ``Conv1D *
    TravelTimeSpray * _BlockOrder``. ``sources (2, ns)`` and ``recs
    (2, nr)`` hold ``(x, z)``. The tables are made on ``device`` (JAX's
    default where ``None``). The event ``lsm.tables`` (``pairs``,
    ``npix``, ``stored``, ``table_bytes``, ``built_on``) says what was
    made."""
    nz, nx, nt = len(z), len(x), len(t)
    order = _BlockOrder((nz, nx), dtype=dtype)
    zz, xx = np.meshgrid(z, x, indexing="ij")
    pix = order.order(np.stack([xx, zz], axis=-1))          # (npix_p, 2)
    inside = order.order(np.ones((nz, nx), bool))
    srcs = np.asarray(sources, dtype=float).T               # (ns, 2)
    rcvs = np.asarray(recs, dtype=float).T                  # (nr, 2)
    npairs = srcs.shape[0] * rcvs.shape[0]
    real = np.dtype(dtype)
    packed = _tables(*(jnp.asarray(a, dtype=real, device=device)
                       for a in (srcs, rcvs, pix)),
                     jnp.asarray(inside, device=device), real.type(vel),
                     real.type(t[1] - t[0]), nt=nt)
    spray = TravelTimeSpray._from_packed(packed, npairs, pix.shape[0], nt,
                                         2, dtype)
    _trace.event("lsm.tables", cat="setup", pairs=npairs, npix=nz * nx,
                 stored="pair", table_bytes=spray.table_bytes,
                 built_on=next(iter(spray._it.devices())).platform)
    conv = Conv1D(spray.dimsd, np.asarray(wav, dtype=dtype), axis=-1,
                  offset=wavcenter, dtype=dtype)
    return conv * spray * order


def MPILSM(z, x, t, sources, recs, vel, wav, wavcenter,
           mesh=None, dtype=np.float32) -> MPIVStack:
    """Distributed LSM operator: sources split over shards, one
    Kirchhoff demigration block per shard, its tables made on the
    shard's device, stacked with ``MPIVStack`` (model BROADCAST, data
    SCATTER — ref ``tutorials/lsm.py``; module docstring: the sharded
    form)."""
    from ..parallel.mesh import default_mesh
    mesh = mesh if mesh is not None else default_mesh()
    P = int(mesh.devices.size)
    sources = np.asarray(sources, dtype=float)
    ns = sources.shape[1]
    if ns < P:
        raise ValueError(f"MPILSM: {ns} source(s) cannot be dealt over a "
                         f"mesh of {P} devices (every shard needs one)")
    # dealt evenly, shard c's tables are made on device c; blocks of
    # two sizes keep the replicated form, whose tables lie together
    devices = mesh.devices.flat if ns % P == 0 else [None] * P
    ops = [KirchhoffDemigration(z, x, t, sources[:, c], recs, vel, wav,
                                wavcenter, dtype=dtype, device=dev)
           for c, dev in zip(np.array_split(np.arange(ns), P), devices)]
    return MPIVStack(ops, mesh=mesh)


def lsm(z, x, t, sources, recs, vel, wav, wavcenter, refl: np.ndarray,
        niter: int = 20, mesh=None,
        dtype=np.float32) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Model data from ``refl`` and invert with CGLS. Returns
    ``(minv, d, cost)`` with ``minv``/``refl`` on the ``(nz, nx)`` grid."""
    Op = MPILSM(z, x, t, sources, recs, vel, wav, wavcenter, mesh=mesh,
                dtype=dtype)
    m = DistributedArray.to_dist(refl.ravel().astype(dtype),
                                 partition=Partition.BROADCAST, mesh=mesh)
    d = Op.matvec(m)
    x0 = DistributedArray.to_dist(np.zeros(Op.shape[1], dtype=dtype),
                                  partition=Partition.BROADCAST, mesh=mesh)
    out = cgls(Op, d, x0=x0, niter=niter, tol=0.0)
    minv, cost = out[0], out[5]
    return (np.asarray(minv.asarray()).reshape(len(z), len(x)),
            np.asarray(d.asarray()), np.asarray(cost))


# the tables travel into jit as arguments (ops/stack.py registers the
# generic MPIVStack's local operators, ops/local.py Conv1D and the
# product); _BlockOrder holds no array
register_operator_arrays(TravelTimeSpray, "_it", "_wt", "_lohi")
register_operator_arrays(_BlockOrder)
