"""Distributed N-D FFTs (pencil decomposition).

Rebuild of ``pylops_mpi/signalprocessing/FFTND.py:22-314``,
``FFT2D.py:11-172`` and ``_baseffts.py:15-134``. The reference delegates
the distributed transform to **mpi4py-fft's PFFT** (FFTW + pencil
decomposition with internal MPI all-to-all transposes) and wraps it with
pylops conventions: unnormalized forward, adjoint = N·ifft (norm
"none") or 1/N-scaled pair (norm "1/n"), √2 scaling of positive
non-Nyquist bins for ``real=True`` (ref ``_scale_real_fft:278-309``),
and per-axis ifftshift-before / fftshift-after.

TPU-native pencil: FFT the non-sharded axes locally, reshard
(``all_to_all``, emitted by XLA for the sharding-constraint change) so
the originally-sharded axis becomes local, FFT it, and ravel back to
the flat axis-0-sharded vector — exactly PFFT's two-pencil dance (ref
``_pfft_in_axis``/``_pfft_out_axis``, ``FFTND.py:199-211``) with the
compiler scheduling the transposes. Local transforms go through
``ops/dft.py`` — XLA's native FFT or the matmul (MXU) DFT engine for
TPU runtimes without an FFT custom-call (fftshift/ifftshift are plain
rolls and stay on ``jnp.fft``).

Planar (complex-free) execution: when the resolved ``fft_mode`` is
``planar`` — what ``auto`` picks on TPU runtimes with no complex
lowering at all (round-5 hardware finding) — the aligned pencil
schedule runs on REAL (re, im) plane pairs end to end: local
transforms call ``dft.fft_planes``/``rfft_planes``/..., each pencil
transpose is ONE stacked real ``all_to_all``
(``parallel.collectives.plane_all_to_all``), and complex dtypes appear
only as ``real``/``imag``/``lax.complex`` representation ops at the
user-facing matvec boundary. Plane-aware callers use
:meth:`_MPIBaseFFTND.matvec_planes` / ``rmatvec_planes`` and get a
program with zero complex-dtype ops, collectives included (pinned by
``tests/test_fft.py::test_planar_pencil_hlo_complex_free``). For real
transforms the all-to-all carries the half-spectrum as two f32 planes
— about half the bytes of the complex engine's full-spectrum c64
schedule (``pencil_fft2d_planar`` bench row).

Pipelined pencil transposes (round 8, ``PYLOPS_MPI_TPU_OVERLAP`` /
``overlap=`` / ``comm_chunks=``): with the overlap enabled, each
aligned-path transpose streams as K tiled ``all_to_all`` chunks along
``out_ax``, every chunk chased immediately by its slice of the axis-0
transform section, so chunk ``k``'s ICI transfer flies while chunk
``k±1`` transforms (arXiv 2112.01075's chunked redistribution;
``parallel.collectives.chunked_pencil_transpose``). K all-to-alls per
transpose are pinned in CI; ``off`` keeps the bulk single-collective
kernels bit-identical, and chunk counts that don't fit the axis fall
back with a logged note.

Hierarchical pencil transposes (round 11,
``PYLOPS_MPI_TPU_HIERARCHICAL`` / ``hierarchical=``): on a HYBRID mesh
(``make_mesh_hybrid`` — a DCN axis over slices times an ICI axis
within each; ``parallel/topology.py``) the aligned pencil path opens
up and every transpose runs the two-level schedule
(``collectives.hier_pencil_transpose``): a local block reorder, the
dense intra-slice all-to-all on the ICI axis, and ONE staged
inter-slice exchange on the DCN axis — bit-identical in result to the
flat combined-axis all-to-all, but each device's DCN traffic drops to
the direct ``(D-1)/D`` share of its shard instead of the full-gather
volume the generic multi-axis reshard pays (the ``_reshard``
note below). ``off`` keeps hybrid meshes on the pre-round-11 generic
path, compiled-HLO bit-identical (pinned); flat meshes never change.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import dft
from ..distributedarray import DistributedArray, Partition
from ..linearoperator import MPILinearOperator
from ..parallel.mesh import axis_sharding
from ..parallel.collectives import all_to_all_resharding
from ..parallel.partition import (local_split, pad_index_map,
                                  unpad_index_map)

__all__ = ["MPIFFTND", "MPIFFT2D"]


def _astuple(v, n, cast=float):
    if np.ndim(v) == 0:
        return (cast(v),) * n
    v = tuple(cast(x) for x in v)
    if len(v) != n:
        raise ValueError(f"expected {n} values, got {len(v)}")
    return v


class _MPIBaseFFTND(MPILinearOperator):
    """Shared bookkeeping (ref ``_baseffts.py:15-134``): nffts, sample
    frequencies ``fs``, real/complex dtypes, norm validation."""

    def __init__(self, dims, axes, nffts=None, sampling=1.0, norm="none",
                 real=False, ifftshift_before=False, fftshift_after=False,
                 mesh=None, dtype="complex128", overlap=None,
                 comm_chunks=None, hierarchical=None):
        if comm_chunks is not None and int(comm_chunks) < 1:
            raise ValueError(f"comm_chunks={comm_chunks}: must be >= 1")
        self.dims_nd = tuple(int(d) for d in np.atleast_1d(dims))
        ndim = len(self.dims_nd)
        axes = tuple(ax % ndim for ax in np.atleast_1d(axes))
        self.axes = np.asarray(axes)
        if nffts is None:
            nffts = tuple(self.dims_nd[ax] for ax in axes)
        self.nffts = _astuple(nffts, len(axes), int)
        self.sampling = _astuple(sampling, len(axes), float)
        if norm == "backward":
            # numpy-convention names get the reference's guidance
            # (ref _baseffts.py:79-84)
            raise ValueError(
                'To use no scaling on the forward transform, use "none". '
                "Note that in this case the adjoint transform will *not* "
                "have a 1/n scaling.")
        if norm == "forward":
            raise ValueError(
                'To use 1/n scaling on the forward transform, use "1/n". '
                "Note that in this case the adjoint transform will *also* "
                "have a 1/n scaling.")
        if isinstance(norm, str) and norm.lower() == "1/n":
            norm = "1/n"   # ref accepts any case (_baseffts.py:77)
        if norm not in ("none", "1/n"):
            raise ValueError(f"norm must be 'none' or '1/n', got {norm!r}")
        self.norm = norm
        self.real = bool(real)
        self.ifftshift_before = np.broadcast_to(
            np.atleast_1d(ifftshift_before), (len(axes),)).copy()
        self.fftshift_after = np.broadcast_to(
            np.atleast_1d(fftshift_after), (len(axes),)).copy()
        # frequency vectors
        self.fs = []
        for i, (ax, nfft, samp) in enumerate(
                zip(axes, self.nffts, self.sampling)):
            if self.real and i == len(axes) - 1:
                f = np.fft.rfftfreq(nfft, d=samp)
            else:
                f = np.fft.fftfreq(nfft, d=samp)
                if self.fftshift_after[i]:
                    f = np.fft.fftshift(f)
            self.fs.append(f)
        dt = np.dtype(dtype)
        self.cdtype = np.result_type(dt, np.complex64)
        self.rdtype = np.real(np.ones(1, dtype=self.cdtype)).dtype \
            if self.real else self.cdtype
        self.clinear = not (self.real or np.issubdtype(dt, np.floating))
        dimsd = list(self.dims_nd)
        for i, ax in enumerate(axes):
            dimsd[ax] = self.nffts[i]
        if self.real:
            dimsd[axes[-1]] = self.nffts[-1] // 2 + 1
        self.dimsd_nd = tuple(dimsd)
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        # pipelined pencil transposes (round 8): when the overlap is
        # enabled the two aligned-path all-to-alls stream as
        # `comm_chunks` tiled chunks interleaved with the per-chunk
        # axis-0 transforms (collectives.chunked_pencil_transpose);
        # off = the bulk single-collective schedule, bit-identical.
        # Autotuner seam (round 10): kwargs left at None consult the
        # plan (PYLOPS_MPI_TPU_TUNE=on|auto); explicit kwargs and the
        # env seams behave exactly as before when tuning is off.
        from ..utils.deps import (overlap_enabled, comm_chunks_default,
                                  overlap_env_pinned,
                                  comm_chunks_env_pinned,
                                  hierarchical_enabled,
                                  hierarchical_env_pinned)
        want_overlap = overlap is None and not overlap_env_pinned()
        want_chunks = comm_chunks is None and not comm_chunks_env_pinned()
        want_hier = (hierarchical is None
                     and not hierarchical_env_pinned())
        if want_overlap or want_chunks or want_hier:
            from ..tuning import plan as _tuneplan
            tplan = _tuneplan.get_plan(
                "fft", shape=self.dims_nd, dtype=self.cdtype,
                mesh=self.mesh,
                extra={"fft_axes": tuple(int(a) for a in self.axes),
                       "real": self.real})
            if tplan is not None:
                if want_overlap \
                        and tplan.get("overlap") in ("on", "off"):
                    overlap = tplan.get("overlap")
                if want_chunks and tplan.get("comm_chunks"):
                    comm_chunks = max(1, int(tplan.get("comm_chunks")))
                if want_hier and tplan.get("hierarchical") in (
                        "auto", "on", "off"):
                    hierarchical = tplan.get("hierarchical")
        self._overlap = overlap_enabled(overlap)
        self._comm_chunks = (int(comm_chunks) if comm_chunks is not None
                             else comm_chunks_default())
        # hierarchical pencil transposes (round 11): active only on a
        # hybrid mesh whose >1-sized axes are exactly (dcn, ici) in
        # mesh order — the linearization hier_pencil_transpose's block
        # reorder is paired against. Off (or any flat mesh) keeps the
        # pre-round-11 paths untouched.
        from ..parallel import topology as _topo
        _h = _topo.hybrid_axes(self.mesh)
        use_hier = _h is not None and hierarchical_enabled(hierarchical)
        if use_hier:
            devshape = np.asarray(self.mesh.devices).shape
            big = [str(n) for n, s in zip(self.mesh.axis_names, devshape)
                   if int(s) > 1]
            use_hier = big == [_h[0], _h[1]]
        self._hier_shape = _h if use_hier else None
        self._hier = use_hier
        self.dims = self.dims_nd
        self.dimsd = self.dimsd_nd
        super().__init__(shape=(int(np.prod(dimsd)), int(np.prod(self.dims_nd))),
                         dtype=self.cdtype)
        # pencil axes (ref FFTND.py:188-211): input sharded on 0 unless
        # the final transform axis IS 0, then on 1
        self._in_axis = 1 if axes[-1] == 0 and ndim > 1 else 0
        if self._in_axis in axes and ndim > 1:
            others = [ax for ax in range(ndim) if ax != self._in_axis]
            self._out_axis = others[0]
        else:
            self._out_axis = self._in_axis
        self._scale = float(np.prod(self.nffts))
        # Row-aligned pencil layouts for the in_axis==0 fast path: when
        # the flat input/output vectors carry these local shapes, the
        # flat <-> cube conversions are pure per-shard reshapes (zero
        # comm) and all data movement is the two explicit all-to-all
        # pencil transposes — ragged sizes included (pad-to-multiple
        # while sharded, crop once local; replaces round 1's full
        # replication fallback, ref mpi4py-fft FFTND.py:188-211).
        P = int(self.mesh.devices.size)
        self._rows_m = tuple(s[0] for s in local_split(
            self.dims_nd, P, Partition.SCATTER, 0))
        self._rows_d = tuple(s[0] for s in local_split(
            self.dimsd_nd, P, Partition.SCATTER, 0))
        from ..parallel.partition import flat_outer_shapes
        inner_m = int(np.prod(self.dims_nd[1:])) if ndim > 1 else 1
        inner_d = int(np.prod(self.dimsd_nd[1:])) if ndim > 1 else 1
        self._mlocals = flat_outer_shapes(self.dims_nd[0], inner_m, P)
        self._dlocals = flat_outer_shapes(self.dimsd_nd[0], inner_d, P)

    @property
    def model_local_shapes(self):
        """Flat per-shard shapes the operator's model side prefers: a
        vector carrying these enters the pencil schedule with a pure
        reshape (zero communication). Outputs of ``rmatvec`` carry them,
        so chained/iterated applications stay aligned; pass to
        ``DistributedArray.to_dist(..., local_shapes=...)`` for inputs."""
        return self._mlocals

    @property
    def data_local_shapes(self):
        """Flat per-shard shapes of the data side (see
        :attr:`model_local_shapes`); ``matvec`` outputs carry them."""
        return self._dlocals

    # ------------------------------------------------------------- helpers
    def _pencil_chunks(self, width: int, P: int) -> int:
        """Effective chunk count for the streamed pencil transposes at
        this operator's settings (1 = bulk): the overlap seam gates it,
        and chunk counts that don't fit the axis fall back with a
        logged note (collectives.resolve_chunks) instead of erroring."""
        if not self._overlap or P <= 1:
            return 1
        from ..parallel.collectives import resolve_chunks
        return resolve_chunks(width, P, self._comm_chunks,
                              where=f"{type(self).__name__} pencil")

    def _shift_axes(self, flags) -> Tuple[int, ...]:
        return tuple(int(ax) for ax, f in zip(self.axes, flags) if f)

    def _scale_real(self, y: jax.Array, inverse: bool) -> jax.Array:
        """√2 scaling of strictly-positive non-Nyquist bins of the real
        axis (ref ``_scale_real_fft``, ``FFTND.py:278-309``)."""
        ax = int(self.axes[-1])
        hi = 1 + (self.nffts[-1] - 1) // 2
        fac = 1 / np.sqrt(2) if inverse else np.sqrt(2)
        ar = jnp.arange(y.shape[ax])
        # pin the mask vector to y's real dtype: a strong f64 vector
        # (np.sqrt gives float64) would silently promote the whole
        # pencil — c64→c128, f32 planes→f64 — right before the
        # all-to-all, doubling the transpose bytes under x64
        rdt = np.real(np.ones(1, dtype=y.dtype)).dtype
        vec = jnp.where((ar >= 1) & (ar < hi), fac, 1.0).astype(rdt)
        shape = [1] * y.ndim
        shape[ax] = y.shape[ax]
        return y * vec.reshape(shape)

    def _reshard(self, g: jax.Array, new_axis: int,
                 cur_axis: Optional[int] = None,
                 cur_pad: int = 0) -> Tuple[jax.Array, int]:
        """Move the distributed dimension to ``new_axis`` (the pencil
        transpose — XLA lowers the sharding change to an all-to-all over
        ICI). Axes that do not tile the mesh are zero-padded to the next
        multiple of the device count while sharded and cropped as soon as
        they become local again (the pad-and-mask idiom of
        ``DistributedArray``; replaces round 1's full-replication
        fallback, ref mpi4py-fft's ragged pencils ``FFTND.py:188-211``).
        Returns ``(g, new_pad)`` where ``new_pad`` is the number of
        trailing zero rows now carried by ``new_axis``."""
        P = int(self.mesh.devices.size)
        new_pad = (-g.shape[new_axis]) % P
        if new_pad:
            padw = [(0, 0)] * g.ndim
            padw[new_axis] = (0, new_pad)
            g = jnp.pad(g, padw)
        if (cur_axis is not None and cur_axis != new_axis and P > 1
                and len(self.mesh.axis_names) == 1):
            # explicit pencil transpose: one lax.all_to_all of the padded
            # tiles — pinned by hand because GSPMD lowers the equivalent
            # pad+constraint+crop sequence to a full-array all-gather
            g = all_to_all_resharding(g, self.mesh, cur_axis, new_axis)
        else:
            try:
                g = lax.with_sharding_constraint(
                    g, axis_sharding(self.mesh, g.ndim, new_axis))
            except Exception:  # outside jit on an abstract mesh
                pass
        if cur_axis is not None and cur_axis != new_axis:
            g = self._crop(g, cur_axis, cur_pad)
        return g, new_pad

    @staticmethod
    def _crop(g: jax.Array, axis: int, pad: int) -> jax.Array:
        if not pad:
            return g
        idx = [slice(None)] * g.ndim
        idx[axis] = slice(0, g.shape[axis] - pad)
        return g[tuple(idx)]

    def _constrain_replicated(self, g: jax.Array) -> jax.Array:
        from ..parallel.mesh import replicated_sharding
        try:
            return lax.with_sharding_constraint(
                g, replicated_sharding(self.mesh))
        except Exception:
            return g

    # ----------------------------------------- aligned path (in_axis == 0)
    # The whole pencil pipeline runs inside ONE shard_map kernel: local
    # transforms are per-block jnp.fft calls (the SPMD partitioner
    # replicates XLA's FFT custom-call even on non-transformed sharded
    # operands, so the implicit path all-gathers — inside shard_map there
    # is no partitioner) and the two pencil transposes are explicit
    # lax.all_to_all ops, ragged axes handled by pad-to-multiple +
    # crop-once-local (ref mpi4py-fft's ragged pencils, FFTND.py:188-211).

    def _aligned_phys(self, x: DistributedArray, dims, rows) -> jax.Array:
        """Physical flat buffer in the row-aligned layout. When ``x``
        already carries it: the buffer itself (zero comm). Otherwise one
        static row-gather re-packs the logical view (the rebalancing
        cost the reference pays in its @reshaped decorator)."""
        P = int(self.mesh.devices.size)
        rmax = max(rows)
        inner = int(np.prod(dims[1:]))
        if (x.partition == Partition.SCATTER and x.axis == 0
                and x.ndim == 1
                and tuple(s[0] for s in x.local_shapes)
                == tuple(r * inner for r in rows)):
            return x._arr
        g = x.array.reshape(dims)
        src, valid = pad_index_map(rows, rmax)
        cube = jnp.take(g, jnp.asarray(src), axis=0)
        m = jnp.asarray(valid).reshape((P * rmax,) + (1,) * (cube.ndim - 1))
        cube = jnp.where(m, cube, jnp.zeros((), dtype=cube.dtype))
        phys = cube.reshape(-1)
        try:
            phys = lax.with_sharding_constraint(
                phys, axis_sharding(self.mesh, 1, 0))
        except Exception:
            pass
        return phys

    def _wrap_flat(self, phys: jax.Array, dimsd, locals_, mesh,
                   dtype) -> DistributedArray:
        """Row-aligned physical flat buffer -> DistributedArray (the
        C-order flatten keeps each shard's pad rows at its flat block
        tail — exactly the pad-to-max layout DistributedArray stores)."""
        y = DistributedArray(global_shape=int(np.prod(dimsd)), mesh=mesh,
                             partition=Partition.SCATTER, axis=0,
                             local_shapes=locals_, dtype=dtype)
        y._arr = y._place(phys.astype(dtype))
        return y

    def _pencil_layout(self):
        """``(axis_name, hier)`` for the aligned kernels: the single
        mesh axis name and ``None`` on a flat mesh; the full axis-name
        tuple (flat buffers shard over every mesh axis) plus the
        ``(dcn_axis, ici_axis, D, I)`` decomposition when the
        hierarchical schedule is active (round 11)."""
        if self._hier_shape is not None:
            return tuple(self.mesh.axis_names), self._hier_shape
        return self.mesh.axis_names[0], None

    @staticmethod
    def _block_transpose(b: jax.Array, axis_name: str, P: int,
                         out_ax: int) -> jax.Array:
        """Inside-kernel pencil transpose: block rows (axis 0) scatter
        over devices, ``out_ax`` tiles gather locally (``out_ax`` padded
        to a device multiple first)."""
        bo = -(-b.shape[out_ax] // P)
        tail = P * bo - b.shape[out_ax]
        if tail:
            padw = [(0, 0)] * b.ndim
            padw[out_ax] = (0, tail)
            b = jnp.pad(b, padw)
        if P > 1:
            b = lax.all_to_all(b, axis_name, split_axis=out_ax,
                               concat_axis=0, tiled=True)
        return b

    @staticmethod
    def _block_transpose_hier(b: jax.Array, hier, out_ax: int) -> jax.Array:
        """Hybrid-mesh :meth:`_block_transpose`: pad ``out_ax`` to a
        device multiple, then the two-level transpose (local reorder +
        intra-slice ICI all-to-all + ONE staged DCN exchange) — result
        bit-identical to the flat combined-axis all-to-all."""
        from ..parallel.collectives import hier_pencil_transpose
        P = int(hier[2]) * int(hier[3])
        bo = -(-b.shape[out_ax] // P)
        tail = P * bo - b.shape[out_ax]
        if tail:
            padw = [(0, 0)] * b.ndim
            padw[out_ax] = (0, tail)
            b = jnp.pad(b, padw)
        return hier_pencil_transpose(b, *hier, out_ax, forward=True)

    @staticmethod
    def _block_transpose_planes_hier(br, bi, hier, out_ax: int):
        """Planar :meth:`_block_transpose_hier` (one stacked real
        collective per fabric phase)."""
        from ..parallel.collectives import hier_pencil_transpose_planes
        P = int(hier[2]) * int(hier[3])
        bo = -(-br.shape[out_ax] // P)
        tail = P * bo - br.shape[out_ax]
        if tail:
            padw = [(0, 0)] * br.ndim
            padw[out_ax] = (0, tail)
            br, bi = jnp.pad(br, padw), jnp.pad(bi, padw)
        return hier_pencil_transpose_planes(br, bi, *hier, out_ax,
                                            forward=True)

    # --------------------------------------------------------------- apply
    def _matvec(self, x: DistributedArray) -> DistributedArray:
        if x.partition != Partition.SCATTER:
            raise ValueError(f"x should have partition={Partition.SCATTER}"
                             f" Got {x.partition} instead...")
        if (len(self.dims_nd) > 1 and self._in_axis == 0
                and (len(self.mesh.axis_names) == 1 or self._hier)):
            return self._matvec_aligned(x)
        return self._matvec_generic(x)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        if x.partition != Partition.SCATTER:
            raise ValueError(f"x should have partition={Partition.SCATTER}"
                             f" Got {x.partition} instead...")
        if (len(self.dims_nd) > 1 and self._in_axis == 0
                and (len(self.mesh.axis_names) == 1 or self._hier)):
            return self._rmatvec_aligned(x)
        return self._rmatvec_generic(x)

    def _matvec_aligned(self, x: DistributedArray) -> DistributedArray:
        """in_axis==0 pencil schedule, one shard_map kernel end to end:
        per-block stage-1 transforms, all-to-all transpose, axis-0
        transform, all-to-all back."""
        if dft.resolved_mode() == "planar":
            return self._matvec_aligned_planar(x)
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec

        axes = [int(a) for a in self.axes]
        shift_before = self._shift_axes(self.ifftshift_before)
        shift_after = self._shift_axes(self.fftshift_after)
        P = int(self.mesh.devices.size)
        axis_name, hier = self._pencil_layout()

        def ridx():
            # linearized device rank of the flat axis-0 sharding: the
            # single mesh axis, or dcn-major (d * I + i) on hybrid
            if hier is None:
                return lax.axis_index(axis_name)
            return (lax.axis_index(hier[0]) * hier[3]
                    + lax.axis_index(hier[1]))

        out_ax = self._out_axis
        rows_m, rows_d = self._rows_m, self._rows_d
        rmax_m, rmax_d = max(rows_m), max(rows_d)
        dims, dimsd = self.dims_nd, self.dimsd_nd
        nfft0 = self.nffts[axes.index(0)] if 0 in axes else None
        # in this path axes[-1] != 0 always (axes[-1]==0 forces
        # in_axis=1), so the (r)fft axis is local in stage 1
        stage1 = [axes[-1]] + [a for a in axes[:-1] if a != 0]
        rows_m_arr = jnp.asarray(rows_m)
        unpad_m = jnp.asarray(unpad_index_map(rows_m, rmax_m))
        pad_d_src, pad_d_valid = pad_index_map(rows_d, rmax_d)
        pad_d_src = jnp.asarray(pad_d_src)
        pad_d_mask = jnp.asarray(pad_d_valid)

        def kernel(xb):
            b = xb.reshape((rmax_m,) + tuple(dims[1:]))
            nrows = rows_m_arr[ridx()]
            row = lax.broadcasted_iota(jnp.int32, b.shape, 0)
            b = jnp.where(row < nrows, b, jnp.zeros((), dtype=b.dtype))
            loc_before = [a for a in shift_before if a != 0]
            if loc_before:
                b = jnp.fft.ifftshift(b, axes=loc_before)
            if not self.clinear:
                b = b.real
            for ax in stage1:
                nfft = self.nffts[axes.index(ax)]
                if self.real and ax == axes[-1]:
                    b = dft.rfft(b, n=nfft, axis=ax)
                else:
                    b = dft.fft(b, n=nfft, axis=ax)
            if self.real:
                b = self._scale_real(b, inverse=False)
            if 0 in axes:
                # the axis-0 section between the two pencil transposes;
                # pure axis-0 work, so it runs unchanged on out_ax tiles
                # when the transpose streams in chunks (overlap on)
                def mid(bb):
                    bb = jnp.take(bb, unpad_m, axis=0)   # exact dims[0]
                    if 0 in shift_before:
                        bb = jnp.fft.ifftshift(bb, axes=(0,))
                    bb = dft.fft(bb, n=nfft0, axis=0)    # exact dimsd[0]
                    if 0 in shift_after:
                        bb = jnp.fft.fftshift(bb, axes=(0,))
                    bb = jnp.take(bb, pad_d_src, axis=0)  # per-shard pad
                    m = pad_d_mask.reshape((-1,) + (1,) * (bb.ndim - 1))
                    return jnp.where(m, bb,
                                     jnp.zeros((), dtype=bb.dtype))

                K = self._pencil_chunks(b.shape[out_ax], P)
                if hier is not None:
                    from ..parallel.collectives import (
                        hier_chunked_pencil_transpose,
                        hier_pencil_transpose)
                    if K > 1:
                        b = hier_chunked_pencil_transpose(
                            b, *hier, out_ax, K, mid)
                    else:
                        b = self._block_transpose_hier(b, hier, out_ax)
                        b = mid(b)
                        b = hier_pencil_transpose(b, *hier, out_ax,
                                                  forward=False)
                elif K > 1:
                    from ..parallel.collectives import \
                        chunked_pencil_transpose
                    b = chunked_pencil_transpose(b, axis_name, P, out_ax,
                                                 K, mid)
                else:
                    b = self._block_transpose(b, axis_name, P, out_ax)
                    b = mid(b)
                    if P > 1:
                        b = lax.all_to_all(b, axis_name, split_axis=0,
                                           concat_axis=out_ax, tiled=True)
                sl = [slice(None)] * b.ndim
                sl[out_ax] = slice(0, dimsd[out_ax])   # crop tail pad
                b = b[tuple(sl)]
            loc_after = [a for a in shift_after if a != 0]
            if loc_after:
                b = jnp.fft.fftshift(b, axes=loc_after)
            if self.norm == "1/n":
                b = b / self._scale
            return b.astype(self.cdtype).reshape(-1)

        phys = self._aligned_phys(x, dims, rows_m)
        out = shard_map(kernel, mesh=self.mesh, in_specs=PSpec(axis_name),
                        out_specs=PSpec(axis_name), check_vma=False)(phys)
        return self._wrap_flat(out, dimsd, self._dlocals, x.mesh,
                               self.cdtype)

    def _rmatvec_aligned(self, x: DistributedArray) -> DistributedArray:
        if dft.resolved_mode() == "planar":
            return self._rmatvec_aligned_planar(x)
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec

        axes = [int(a) for a in self.axes]
        shift_before = self._shift_axes(self.ifftshift_before)
        shift_after = self._shift_axes(self.fftshift_after)
        P = int(self.mesh.devices.size)
        axis_name, hier = self._pencil_layout()

        def ridx():
            # linearized device rank of the flat axis-0 sharding: the
            # single mesh axis, or dcn-major (d * I + i) on hybrid
            if hier is None:
                return lax.axis_index(axis_name)
            return (lax.axis_index(hier[0]) * hier[3]
                    + lax.axis_index(hier[1]))

        out_ax = self._out_axis
        rows_m, rows_d = self._rows_m, self._rows_d
        rmax_m, rmax_d = max(rows_m), max(rows_d)
        dims, dimsd = self.dims_nd, self.dimsd_nd
        nfft0 = self.nffts[axes.index(0)] if 0 in axes else None
        rows_d_arr = jnp.asarray(rows_d)
        unpad_d = jnp.asarray(unpad_index_map(rows_d, rmax_d))
        pad_m_src, pad_m_valid = pad_index_map(rows_m, rmax_m)
        pad_m_src = jnp.asarray(pad_m_src)
        pad_m_mask = jnp.asarray(pad_m_valid)

        def kernel(xb):
            b = xb.reshape((rmax_d,) + tuple(dimsd[1:]))
            nrows = rows_d_arr[ridx()]
            row = lax.broadcasted_iota(jnp.int32, b.shape, 0)
            b = jnp.where(row < nrows, b, jnp.zeros((), dtype=b.dtype))
            loc_after = [a for a in shift_after if a != 0]
            if loc_after:
                b = jnp.fft.ifftshift(b, axes=loc_after)
            if self.real:
                b = self._scale_real(b, inverse=True)
            if 0 in axes:
                def mid(bb):
                    bb = jnp.take(bb, unpad_d, axis=0)   # exact dimsd[0]
                    if 0 in shift_after:
                        bb = jnp.fft.ifftshift(bb, axes=(0,))
                    bb = dft.ifft(bb, n=nfft0, axis=0)
                    bb = bb[:dims[0]]
                    if 0 in shift_before:
                        bb = jnp.fft.fftshift(bb, axes=(0,))
                    bb = jnp.take(bb, pad_m_src, axis=0)  # per-shard pad
                    m = pad_m_mask.reshape((-1,) + (1,) * (bb.ndim - 1))
                    return jnp.where(m, bb,
                                     jnp.zeros((), dtype=bb.dtype))

                K = self._pencil_chunks(b.shape[out_ax], P)
                if hier is not None:
                    from ..parallel.collectives import (
                        hier_chunked_pencil_transpose,
                        hier_pencil_transpose)
                    if K > 1:
                        b = hier_chunked_pencil_transpose(
                            b, *hier, out_ax, K, mid)
                    else:
                        b = self._block_transpose_hier(b, hier, out_ax)
                        b = mid(b)
                        b = hier_pencil_transpose(b, *hier, out_ax,
                                                  forward=False)
                elif K > 1:
                    from ..parallel.collectives import \
                        chunked_pencil_transpose
                    b = chunked_pencil_transpose(b, axis_name, P, out_ax,
                                                 K, mid)
                else:
                    b = self._block_transpose(b, axis_name, P, out_ax)
                    b = mid(b)
                    if P > 1:
                        b = lax.all_to_all(b, axis_name, split_axis=0,
                                           concat_axis=out_ax, tiled=True)
                sl = [slice(None)] * b.ndim
                sl[out_ax] = slice(0, dimsd[out_ax])   # crop tail pad
                b = b[tuple(sl)]
            for ax in [a for a in axes[:-1] if a != 0][::-1]:
                b = dft.ifft(b, n=self.nffts[axes.index(ax)], axis=ax)
            if self.real:
                b = dft.irfft(b, n=self.nffts[-1], axis=axes[-1])
            else:
                b = dft.ifft(b, n=self.nffts[-1], axis=axes[-1])
            # crop local axes to model dims (nfft may exceed dims);
            # axis 0 was cropped while assembled in the transpose stage
            b = b[(slice(None),) + tuple(slice(0, d) for d in dims[1:])]
            if self.norm == "none":
                b = b * self._scale  # cancel ifft's 1/N: true adjoint
            if not self.clinear:
                b = b.real
            loc_before = [a for a in shift_before if a != 0]
            if loc_before:
                b = jnp.fft.fftshift(b, axes=loc_before)
            dt = self.rdtype if not self.clinear else self.cdtype
            return b.astype(dt).reshape(-1)

        phys = self._aligned_phys(x, dimsd, rows_d)
        out = shard_map(kernel, mesh=self.mesh, in_specs=PSpec(axis_name),
                        out_specs=PSpec(axis_name), check_vma=False)(phys)
        dtype = self.rdtype if not self.clinear else self.cdtype
        return self._wrap_flat(out, dims, self._mlocals, x.mesh, dtype)

    # ----------------------------------------- planar (plane-pair) path
    # The aligned pencil schedule on REAL (re, im) plane pairs: local
    # transforms through dft.fft_planes/rfft_planes/irfft_planes, each
    # pencil transpose ONE stacked real all-to-all (plane_all_to_all),
    # no complex dtype anywhere inside the shard_map program — built
    # for TPU runtimes with no complex lowering at all (ops/dft.py
    # module docstring, round-5 hardware finding). The complex-facing
    # matvec/rmatvec convert with real/imag/lax.complex at the user
    # boundary only; plane-aware callers (matvec_planes/rmatvec_planes)
    # get a fully complex-free compiled program.

    def _planes_path_ok(self) -> bool:
        return (len(self.dims_nd) > 1 and self._in_axis == 0
                and (len(self.mesh.axis_names) == 1 or self._hier))

    @staticmethod
    def _block_transpose_planes(br, bi, axis_name: str, P: int,
                                out_ax: int):
        """Planar :meth:`_block_transpose`: pad ``out_ax`` to a device
        multiple on both planes, then ONE stacked all-to-all."""
        from ..parallel.collectives import plane_all_to_all
        bo = -(-br.shape[out_ax] // P)
        tail = P * bo - br.shape[out_ax]
        if tail:
            padw = [(0, 0)] * br.ndim
            padw[out_ax] = (0, tail)
            br, bi = jnp.pad(br, padw), jnp.pad(bi, padw)
        if P > 1:
            br, bi = plane_all_to_all(br, bi, axis_name,
                                      split_axis=out_ax, concat_axis=0)
        return br, bi

    def _planes_fwd_phys(self, xr: jax.Array, xi: Optional[jax.Array]):
        """Planar forward pencil on row-aligned flat PHYSICAL plane
        buffers (``xi`` None = zero imaginary plane, no buffer ever
        materialized for it); returns the flat (yr, yi) data-side
        planes. Mirrors the complex kernel of :meth:`_matvec_aligned`
        stage for stage."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec
        from ..parallel.collectives import plane_all_to_all

        axes = [int(a) for a in self.axes]
        shift_before = self._shift_axes(self.ifftshift_before)
        shift_after = self._shift_axes(self.fftshift_after)
        P = int(self.mesh.devices.size)
        axis_name, hier = self._pencil_layout()

        def ridx():
            # linearized device rank of the flat axis-0 sharding: the
            # single mesh axis, or dcn-major (d * I + i) on hybrid
            if hier is None:
                return lax.axis_index(axis_name)
            return (lax.axis_index(hier[0]) * hier[3]
                    + lax.axis_index(hier[1]))

        out_ax = self._out_axis
        rows_m, rows_d = self._rows_m, self._rows_d
        rmax_m, rmax_d = max(rows_m), max(rows_d)
        dims, dimsd = self.dims_nd, self.dimsd_nd
        nfft0 = self.nffts[axes.index(0)] if 0 in axes else None
        stage1 = [axes[-1]] + [a for a in axes[:-1] if a != 0]
        rows_m_arr = jnp.asarray(rows_m)
        unpad_m = jnp.asarray(unpad_index_map(rows_m, rmax_m))
        pad_d_src, pad_d_valid = pad_index_map(rows_d, rmax_d)
        pad_d_src = jnp.asarray(pad_d_src)
        pad_d_mask = jnp.asarray(pad_d_valid)
        pdt = dft.plane_dtype(self.cdtype)

        def kernel(*planes):
            br = planes[0].reshape((rmax_m,) + tuple(dims[1:]))
            bi = (planes[1].reshape(br.shape) if len(planes) > 1
                  else None)
            nrows = rows_m_arr[ridx()]
            row = lax.broadcasted_iota(jnp.int32, br.shape, 0)

            def scrub(p):
                return jnp.where(row < nrows, p,
                                 jnp.zeros((), dtype=p.dtype))

            br = scrub(br)
            bi = scrub(bi) if bi is not None else None
            loc_before = [a for a in shift_before if a != 0]
            if loc_before:
                br = jnp.fft.ifftshift(br, axes=loc_before)
                if bi is not None:
                    bi = jnp.fft.ifftshift(bi, axes=loc_before)
            if not self.clinear:
                bi = None  # the complex kernel's b.real
            for ax in stage1:
                nfft = self.nffts[axes.index(ax)]
                if self.real and ax == axes[-1]:
                    br, bi = dft.rfft_planes(br, n=nfft, axis=ax)
                else:
                    br, bi = dft.fft_planes(br, bi, n=nfft, axis=ax)
            if self.real:
                br = self._scale_real(br, inverse=False)
                bi = self._scale_real(bi, inverse=False)
            if 0 in axes:
                def mid(pr_, pi_):
                    pr_ = jnp.take(pr_, unpad_m, axis=0)  # exact dims[0]
                    pi_ = jnp.take(pi_, unpad_m, axis=0)
                    if 0 in shift_before:
                        pr_ = jnp.fft.ifftshift(pr_, axes=(0,))
                        pi_ = jnp.fft.ifftshift(pi_, axes=(0,))
                    pr_, pi_ = dft.fft_planes(pr_, pi_, n=nfft0, axis=0)
                    if 0 in shift_after:
                        pr_ = jnp.fft.fftshift(pr_, axes=(0,))
                        pi_ = jnp.fft.fftshift(pi_, axes=(0,))
                    pr_ = jnp.take(pr_, pad_d_src, axis=0)  # per-shard
                    pi_ = jnp.take(pi_, pad_d_src, axis=0)
                    m = pad_d_mask.reshape((-1,) + (1,) * (pr_.ndim - 1))
                    pr_ = jnp.where(m, pr_, jnp.zeros((), dtype=pr_.dtype))
                    pi_ = jnp.where(m, pi_, jnp.zeros((), dtype=pi_.dtype))
                    return pr_, pi_

                K = self._pencil_chunks(br.shape[out_ax], P)
                if hier is not None:
                    from ..parallel.collectives import (
                        hier_chunked_pencil_transpose_planes,
                        hier_pencil_transpose_planes)
                    if K > 1:
                        br, bi = hier_chunked_pencil_transpose_planes(
                            br, bi, *hier, out_ax, K, mid)
                    else:
                        br, bi = self._block_transpose_planes_hier(
                            br, bi, hier, out_ax)
                        br, bi = mid(br, bi)
                        br, bi = hier_pencil_transpose_planes(
                            br, bi, *hier, out_ax, forward=False)
                elif K > 1:
                    from ..parallel.collectives import \
                        chunked_pencil_transpose_planes
                    br, bi = chunked_pencil_transpose_planes(
                        br, bi, axis_name, P, out_ax, K, mid)
                else:
                    br, bi = self._block_transpose_planes(br, bi,
                                                          axis_name,
                                                          P, out_ax)
                    br, bi = mid(br, bi)
                    if P > 1:
                        br, bi = plane_all_to_all(br, bi, axis_name,
                                                  split_axis=0,
                                                  concat_axis=out_ax)
                sl = [slice(None)] * br.ndim
                sl[out_ax] = slice(0, dimsd[out_ax])   # crop tail pad
                br, bi = br[tuple(sl)], bi[tuple(sl)]
            loc_after = [a for a in shift_after if a != 0]
            if loc_after:
                br = jnp.fft.fftshift(br, axes=loc_after)
                bi = jnp.fft.fftshift(bi, axes=loc_after)
            if self.norm == "1/n":
                br, bi = br / self._scale, bi / self._scale
            return (br.astype(pdt).reshape(-1),
                    bi.astype(pdt).reshape(-1))

        planes = (xr,) if xi is None else (xr, xi)
        spec = PSpec(axis_name)
        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(spec,) * len(planes),
                         out_specs=(spec, spec),
                         check_vma=False)(*planes)

    def _planes_adj_phys(self, xr: jax.Array, xi: Optional[jax.Array]):
        """Planar adjoint pencil on flat physical plane buffers;
        returns a 1-tuple (real-model operators) or 2-tuple of flat
        model-side planes. Mirrors :meth:`_rmatvec_aligned`."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec
        from ..parallel.collectives import plane_all_to_all

        axes = [int(a) for a in self.axes]
        shift_before = self._shift_axes(self.ifftshift_before)
        shift_after = self._shift_axes(self.fftshift_after)
        P = int(self.mesh.devices.size)
        axis_name, hier = self._pencil_layout()

        def ridx():
            # linearized device rank of the flat axis-0 sharding: the
            # single mesh axis, or dcn-major (d * I + i) on hybrid
            if hier is None:
                return lax.axis_index(axis_name)
            return (lax.axis_index(hier[0]) * hier[3]
                    + lax.axis_index(hier[1]))

        out_ax = self._out_axis
        rows_m, rows_d = self._rows_m, self._rows_d
        rmax_m, rmax_d = max(rows_m), max(rows_d)
        dims, dimsd = self.dims_nd, self.dimsd_nd
        nfft0 = self.nffts[axes.index(0)] if 0 in axes else None
        rows_d_arr = jnp.asarray(rows_d)
        unpad_d = jnp.asarray(unpad_index_map(rows_d, rmax_d))
        pad_m_src, pad_m_valid = pad_index_map(rows_m, rmax_m)
        pad_m_src = jnp.asarray(pad_m_src)
        pad_m_mask = jnp.asarray(pad_m_valid)
        out_dt = self.rdtype if not self.clinear else self.cdtype
        pdt = dft.plane_dtype(out_dt)

        def kernel(*planes):
            br = planes[0].reshape((rmax_d,) + tuple(dimsd[1:]))
            bi = (planes[1].reshape(br.shape) if len(planes) > 1
                  else None)
            nrows = rows_d_arr[ridx()]
            row = lax.broadcasted_iota(jnp.int32, br.shape, 0)

            def scrub(p):
                return jnp.where(row < nrows, p,
                                 jnp.zeros((), dtype=p.dtype))

            br = scrub(br)
            bi = scrub(bi) if bi is not None else None
            loc_after = [a for a in shift_after if a != 0]
            if loc_after:
                br = jnp.fft.ifftshift(br, axes=loc_after)
                if bi is not None:
                    bi = jnp.fft.ifftshift(bi, axes=loc_after)
            if self.real:
                br = self._scale_real(br, inverse=True)
                if bi is not None:
                    bi = self._scale_real(bi, inverse=True)
            if 0 in axes:
                if bi is None:  # axis-0 transform mixes both planes
                    bi = jnp.zeros_like(br)

                def mid(pr_, pi_):
                    pr_ = jnp.take(pr_, unpad_d, axis=0)  # exact dimsd[0]
                    pi_ = jnp.take(pi_, unpad_d, axis=0)
                    if 0 in shift_after:
                        pr_ = jnp.fft.ifftshift(pr_, axes=(0,))
                        pi_ = jnp.fft.ifftshift(pi_, axes=(0,))
                    pr_, pi_ = dft.ifft_planes(pr_, pi_, n=nfft0, axis=0)
                    pr_, pi_ = pr_[:dims[0]], pi_[:dims[0]]
                    if 0 in shift_before:
                        pr_ = jnp.fft.fftshift(pr_, axes=(0,))
                        pi_ = jnp.fft.fftshift(pi_, axes=(0,))
                    pr_ = jnp.take(pr_, pad_m_src, axis=0)  # per-shard
                    pi_ = jnp.take(pi_, pad_m_src, axis=0)
                    m = pad_m_mask.reshape((-1,) + (1,) * (pr_.ndim - 1))
                    pr_ = jnp.where(m, pr_, jnp.zeros((), dtype=pr_.dtype))
                    pi_ = jnp.where(m, pi_, jnp.zeros((), dtype=pi_.dtype))
                    return pr_, pi_

                K = self._pencil_chunks(br.shape[out_ax], P)
                if hier is not None:
                    from ..parallel.collectives import (
                        hier_chunked_pencil_transpose_planes,
                        hier_pencil_transpose_planes)
                    if K > 1:
                        br, bi = hier_chunked_pencil_transpose_planes(
                            br, bi, *hier, out_ax, K, mid)
                    else:
                        br, bi = self._block_transpose_planes_hier(
                            br, bi, hier, out_ax)
                        br, bi = mid(br, bi)
                        br, bi = hier_pencil_transpose_planes(
                            br, bi, *hier, out_ax, forward=False)
                elif K > 1:
                    from ..parallel.collectives import \
                        chunked_pencil_transpose_planes
                    br, bi = chunked_pencil_transpose_planes(
                        br, bi, axis_name, P, out_ax, K, mid)
                else:
                    br, bi = self._block_transpose_planes(br, bi,
                                                          axis_name,
                                                          P, out_ax)
                    br, bi = mid(br, bi)
                    if P > 1:
                        br, bi = plane_all_to_all(br, bi, axis_name,
                                                  split_axis=0,
                                                  concat_axis=out_ax)
                sl = [slice(None)] * br.ndim
                sl[out_ax] = slice(0, dimsd[out_ax])   # crop tail pad
                br, bi = br[tuple(sl)], bi[tuple(sl)]
            for ax in [a for a in axes[:-1] if a != 0][::-1]:
                br, bi = dft.ifft_planes(br, bi,
                                         n=self.nffts[axes.index(ax)],
                                         axis=ax)
            if self.real:
                if bi is None:
                    bi = jnp.zeros_like(br)
                br = dft.irfft_planes(br, bi, n=self.nffts[-1],
                                      axis=axes[-1])
                bi = None
            else:
                br, bi = dft.ifft_planes(br, bi, n=self.nffts[-1],
                                         axis=axes[-1])
            crop = (slice(None),) + tuple(slice(0, d) for d in dims[1:])
            br = br[crop]
            bi = bi[crop] if bi is not None else None
            if self.norm == "none":
                br = br * self._scale  # cancel ifft's 1/N: true adjoint
                if bi is not None:
                    bi = bi * self._scale
            if not self.clinear:
                bi = None  # the complex kernel's b.real
            loc_before = [a for a in shift_before if a != 0]
            if loc_before:
                br = jnp.fft.fftshift(br, axes=loc_before)
                if bi is not None:
                    bi = jnp.fft.fftshift(bi, axes=loc_before)
            if bi is None:
                return (br.astype(pdt).reshape(-1),)
            return (br.astype(pdt).reshape(-1),
                    bi.astype(pdt).reshape(-1))

        planes = (xr,) if xi is None else (xr, xi)
        spec = PSpec(axis_name)
        n_out = 1 if not self.clinear else 2
        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(spec,) * len(planes),
                         out_specs=(spec,) * n_out,
                         check_vma=False)(*planes)

    def _matvec_aligned_planar(self, x: DistributedArray) -> DistributedArray:
        """Complex-facing forward over the planar pencil: split into
        (re, im) planes at the user boundary, run the complex-free
        plane program, materialize the output with one ``lax.complex``
        — the only complex-dtype ops in the apply are these boundary
        representation ops (plane-aware callers use
        :meth:`matvec_planes` and skip even those)."""
        pdt = dft.plane_dtype(self.cdtype)
        phys = self._aligned_phys(x, self.dims_nd, self._rows_m)
        if jnp.iscomplexobj(phys):
            xr = jnp.real(phys).astype(pdt)
            xi = jnp.imag(phys).astype(pdt)
        else:
            xr, xi = phys.astype(pdt), None
        yr, yi = self._planes_fwd_phys(xr, xi)
        return self._wrap_flat(lax.complex(yr, yi), self.dimsd_nd,
                               self._dlocals, x.mesh, self.cdtype)

    def _rmatvec_aligned_planar(self, x: DistributedArray) -> DistributedArray:
        pdt = dft.plane_dtype(self.cdtype)
        phys = self._aligned_phys(x, self.dimsd_nd, self._rows_d)
        if jnp.iscomplexobj(phys):
            xr = jnp.real(phys).astype(pdt)
            xi = jnp.imag(phys).astype(pdt)
        else:
            xr, xi = phys.astype(pdt), None
        planes = self._planes_adj_phys(xr, xi)
        dt = self.rdtype if not self.clinear else self.cdtype
        out = planes[0] if len(planes) == 1 else lax.complex(*planes)
        return self._wrap_flat(out, self.dims_nd, self._mlocals, x.mesh,
                               dt)

    def matvec_planes(self, xr: DistributedArray,
                      xi: Optional[DistributedArray] = None):
        """Plane-pair forward apply: REAL (re, im) flat DistributedArray
        planes in, plane-pair DistributedArrays out. The compiled
        program contains NO complex dtype anywhere — collectives
        included — which is what FFT-less/complex-less TPU runtimes and
        plane-aware operator chains consume (pinned by
        ``tests/test_fft.py::test_planar_pencil_hlo_complex_free``).
        Runs the planar engine regardless of the resolved mode.
        ``xi=None`` means a zero imaginary plane (required for
        ``real=True`` operators, whose model is real). Requires the
        aligned pencil path (ndim > 1, single-axis mesh, in_axis==0)."""
        self._check_planes_args(xr, xi, self.shape[1])
        if self.real and xi is not None:
            raise ValueError("real=True operators take a real model: "
                             "pass xi=None")
        pdt = dft.plane_dtype(self.cdtype)
        pr = self._aligned_phys(xr, self.dims_nd,
                                self._rows_m).astype(pdt)
        pi = (None if xi is None else
              self._aligned_phys(xi, self.dims_nd,
                                 self._rows_m).astype(pdt))
        yr, yi = self._planes_fwd_phys(pr, pi)
        return (self._wrap_flat(yr, self.dimsd_nd, self._dlocals,
                                xr.mesh, pdt),
                self._wrap_flat(yi, self.dimsd_nd, self._dlocals,
                                xr.mesh, pdt))

    def rmatvec_planes(self, xr: DistributedArray,
                       xi: Optional[DistributedArray] = None):
        """Plane-pair adjoint apply (see :meth:`matvec_planes`);
        returns ``(yr, None)`` for real-model operators, whose adjoint
        output is a single real plane."""
        self._check_planes_args(xr, xi, self.shape[0])
        pdt = dft.plane_dtype(self.cdtype)
        pr = self._aligned_phys(xr, self.dimsd_nd,
                                self._rows_d).astype(pdt)
        pi = (None if xi is None else
              self._aligned_phys(xi, self.dimsd_nd,
                                 self._rows_d).astype(pdt))
        planes = self._planes_adj_phys(pr, pi)
        out_dt = self.rdtype if not self.clinear else self.cdtype
        pdt_out = dft.plane_dtype(out_dt)
        yr = self._wrap_flat(planes[0], self.dims_nd, self._mlocals,
                             xr.mesh, pdt_out)
        yi = (self._wrap_flat(planes[1], self.dims_nd, self._mlocals,
                              xr.mesh, pdt_out)
              if len(planes) > 1 else None)
        return yr, yi

    def _check_planes_args(self, xr, xi, n: int) -> None:
        if not self._planes_path_ok():
            raise NotImplementedError(
                "plane-pair apply requires the aligned pencil path "
                "(ndim > 1 with in_axis == 0 on a single-axis mesh, or "
                "a hybrid mesh with the hierarchical schedule enabled)")
        for p in (xr, xi):
            if p is None:
                continue
            if p.partition != Partition.SCATTER:
                raise ValueError(f"planes should have partition="
                                 f"{Partition.SCATTER} Got {p.partition}"
                                 " instead...")
            if p.global_shape != (n,):
                raise ValueError(f"plane global shape {p.global_shape} "
                                 f"!= expected ({n},)")

    def _matvec_generic(self, x: DistributedArray) -> DistributedArray:
        """General pencil schedule on the logical global array (1-D
        transforms and the rare in_axis==1 layout): XLA partitions the
        traced program; the explicit transposes still pin all-to-alls."""
        g = x.array.reshape(self.dims_nd)
        if self.ifftshift_before.any():
            g = jnp.fft.ifftshift(
                g, axes=self._shift_axes(self.ifftshift_before))
        if not self.clinear:
            g = g.real
        axes = [int(a) for a in self.axes]
        in_ax = self._in_axis
        # Two-pencil schedule. Invariant: never FFT along the currently
        # sharded axis (XLA cannot partition the FFT custom-call through
        # its transform axis). Stage 1: sharded on in_ax, transform every
        # other axis locally — the (r)fft axis (axes[-1]) first, on the
        # real input. Stage 2: reshard (all-to-all) so in_ax is local,
        # transform it.
        pad = 0
        if g.ndim == 1:
            g = self._constrain_replicated(g)
        else:
            g, pad = self._reshard(g, in_ax)
        stage1 = ([axes[-1]] if axes[-1] != in_ax else []) + \
            [a for a in axes[:-1] if a != in_ax]
        for ax in stage1:
            nfft = self.nffts[axes.index(ax)]
            if self.real and ax == axes[-1]:
                g = dft.rfft(g, n=nfft, axis=ax)
            else:
                g = dft.fft(g, n=nfft, axis=ax)
        if in_ax in axes:
            if g.ndim > 1:  # pencil transpose; in_ax padding cropped
                g, pad = self._reshard(g, self._out_axis, in_ax, pad)
            nfft = self.nffts[axes.index(in_ax)]
            if self.real and in_ax == axes[-1]:
                g = dft.rfft(g, n=nfft, axis=in_ax)
            else:
                g = dft.fft(g, n=nfft, axis=in_ax)
            if g.ndim > 1:
                g = self._crop(g, self._out_axis, pad)
        elif g.ndim > 1:
            g = self._crop(g, in_ax, pad)
        if self.real:
            g = self._scale_real(g, inverse=False)
        if self.norm == "1/n":
            g = g / self._scale
        if self.fftshift_after.any():
            g = jnp.fft.fftshift(g, axes=self._shift_axes(self.fftshift_after))
        y = DistributedArray(global_shape=self.shape[0], mesh=x.mesh,
                             partition=Partition.SCATTER, axis=0,
                             dtype=self.cdtype)
        y[:] = g.astype(self.cdtype).ravel()
        return y

    def _rmatvec_generic(self, x: DistributedArray) -> DistributedArray:
        g = x.array.reshape(self.dimsd_nd)
        if self.fftshift_after.any():
            g = jnp.fft.ifftshift(
                g, axes=self._shift_axes(self.fftshift_after))
        if self.real:
            g = self._scale_real(g, inverse=True)
        axes = [int(a) for a in self.axes]
        in_ax = self._in_axis
        # Mirror of the forward schedule: undo in_ax while sharded
        # elsewhere, then reshard and undo the remaining (local) axes,
        # the (i)rfft axis last.
        if g.ndim == 1:
            g = self._constrain_replicated(g)
            if self.real:
                g = dft.irfft(g, n=self.nffts[-1], axis=0)
            else:
                g = dft.ifft(g, n=self.nffts[-1], axis=0)
        else:
            pad = 0
            if in_ax in axes:
                g, pad = self._reshard(g, self._out_axis)
                nfft = self.nffts[axes.index(in_ax)]
                if self.real and in_ax == axes[-1]:
                    g = dft.irfft(g, n=nfft, axis=in_ax)
                else:
                    g = dft.ifft(g, n=nfft, axis=in_ax)
            g, pad = self._reshard(g, in_ax, self._out_axis, pad)
            for ax in [a for a in axes[:-1] if a != in_ax][::-1]:
                g = dft.ifft(g, n=self.nffts[axes.index(ax)], axis=ax)
            if axes[-1] != in_ax:
                if self.real:
                    g = dft.irfft(g, n=self.nffts[-1], axis=axes[-1])
                else:
                    g = dft.ifft(g, n=self.nffts[-1], axis=axes[-1])
            g = self._crop(g, in_ax, pad)
        # crop to model dims (nfft may exceed dims)
        idx = tuple(slice(0, d) for d in self.dims_nd)
        g = g[idx]
        if self.norm == "none":
            g = g * self._scale  # cancel ifft's 1/N: true adjoint
        if not self.clinear:
            g = g.real
        if self.ifftshift_before.any():
            g = jnp.fft.fftshift(
                g, axes=self._shift_axes(self.ifftshift_before))
        y = DistributedArray(global_shape=self.shape[1], mesh=x.mesh,
                             partition=Partition.SCATTER, axis=0,
                             dtype=self.rdtype if not self.clinear else self.cdtype)
        y[:] = g.astype(y.dtype).ravel()
        return y


class MPIFFTND(_MPIBaseFFTND):
    """N-dimensional distributed FFT (ref ``FFTND.py:22-314``)."""

    def __init__(self, dims, axes=(0, 1, 2), nffts=None, sampling=1.0,
                 norm="none", real=False, ifftshift_before=False,
                 fftshift_after=False, mesh=None, dtype="complex128",
                 overlap=None, comm_chunks=None, hierarchical=None):
        super().__init__(dims=dims, axes=axes, nffts=nffts, sampling=sampling,
                         norm=norm, real=real,
                         ifftshift_before=ifftshift_before,
                         fftshift_after=fftshift_after, mesh=mesh,
                         dtype=dtype, overlap=overlap,
                         comm_chunks=comm_chunks,
                         hierarchical=hierarchical)


class MPIFFT2D(_MPIBaseFFTND):
    """2-dimensional distributed FFT (ref ``FFT2D.py:11-172``)."""

    def __init__(self, dims, axes=(0, 1), nffts=None, sampling=1.0,
                 norm="none", real=False, ifftshift_before=False,
                 fftshift_after=False, mesh=None, dtype="complex128",
                 overlap=None, comm_chunks=None, hierarchical=None):
        if len(np.atleast_1d(axes)) != 2:
            raise ValueError("MPIFFT2D requires exactly two axes")
        super().__init__(dims=dims, axes=axes, nffts=nffts, sampling=sampling,
                         norm=norm, real=real,
                         ifftshift_before=ifftshift_before,
                         fftshift_after=fftshift_after, mesh=mesh,
                         dtype=dtype, overlap=overlap,
                         comm_chunks=comm_chunks,
                         hierarchical=hierarchical)


# array-less pytree registration (shift/scale factors are rebuilt from
# static shape metadata at trace time)
from ..linearoperator import register_operator_arrays  # noqa: E402
register_operator_arrays(MPIFFTND)
register_operator_arrays(MPIFFT2D)
