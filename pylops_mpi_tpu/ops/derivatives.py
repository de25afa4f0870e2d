"""Distributed derivative operators.

Rebuild of ``pylops_mpi/basicoperators/FirstDerivative.py:18-318``,
``SecondDerivative.py:13-256``, ``Laplacian.py:15-126`` and
``Gradient.py:21-118``.

The reference implements every stencil with explicit **ghost cells**:
``add_ghost_cells`` Send/Recvs one or two boundary rows from the
neighbouring ranks, then each rank applies the stencil to its padded
shard (SURVEY §3.3). On a mesh, the stencil is written once on the
logical global array and XLA's SPMD partitioner inserts the halo
exchanges (collective-permutes over ICI) itself — the ``ppermute``
schedule the reference hand-codes falls out of the compiler. The
``reshaped`` decorator's rebalancing machinery
(ref ``utils/decorators.py:9-86``) dissolves: the flat→N-D→flat
round-trip is a reshape of the logical array.

Distribution is along axis 0 of the N-D layout, as in the reference;
derivatives along non-distributed axes (used by Laplacian/Gradient)
reuse the same local stencils, which XLA partitions trivially (no comm).

One operator does not leave its stencil to XLA: the centered,
``edge=False`` :class:`MPILaplacian` of a 2-D or 3-D array in a
balanced axis-0 split is ONE Pallas pass over each shard's cube,
forward and adjoint (kernel ``pmt_laplacian``, inside a ``shard_map``
with the two ghost planes as operands; 2 volumes live in an apply). Nine
shifted slices and three pads over three axes, two of them at lane and
sublane offsets 1 and 2, are not one pass over the cube for XLA: five
to seven volume passes' worth on a TPU v5e (PERF.md section 6, PR 33).
Every other kind, edge and layout keeps the sum of slices.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from ..distributedarray import DistributedArray, Partition, local_split
from ..stacked import StackedDistributedArray
from ..linearoperator import MPILinearOperator
from ..diagnostics import trace as _trace
from .local import (FirstDerivative as _LocalFirst,
                    SecondDerivative as _LocalSecond)
from .stack import MPIStackedVStack

__all__ = ["MPIFirstDerivative", "MPISecondDerivative", "MPILaplacian",
           "MPIGradient"]


def _tuplize(dims) -> Tuple[int, ...]:
    return tuple(int(d) for d in np.atleast_1d(dims))


def _stencil_spec(op) -> Optional[dict]:
    """Uniform description of every supported axis-0 stencil as

    ``y = Z · S x + E x``

    where ``S`` is the pure interior stencil with zero boundary
    condition (``taps``: input-offset → coefficient), ``Z`` zeroes the
    first ``lo_z`` / last ``hi_z`` output rows, and ``E`` is the sparse
    ``edge=True`` boundary matrix given as ``(out, in, coeff)`` triples
    with rows addressed as ``("lo", i)`` = global row ``i`` or
    ``("hi", i)`` = global row ``n-1-i``. The adjoint needs no separate
    derivation: ``(Z·S)ᴴ = Sᵀ·Z`` (zero the masked *input* rows, run the
    offset-reversed taps) and ``Eᴴ`` is the transposed triples. ``w`` is
    the halo width = max |tap offset|.

    Coefficient tables mirror the local scatter-free stencils in
    ``ops/local.py`` (ref ``FirstDerivative.py:141-318``,
    ``SecondDerivative.py:78-240``)."""
    s = float(op.sampling)
    if isinstance(op, _LocalFirst):
        if op.kind == "forward":
            return dict(w=1, taps={1: 1 / s, 0: -1 / s},
                        lo_z=0, hi_z=1, edge=[])
        if op.kind == "backward":
            return dict(w=1, taps={0: 1 / s, -1: -1 / s},
                        lo_z=1, hi_z=0, edge=[])
        if op.order == 3:
            spec = dict(w=1, taps={1: 1 / (2 * s), -1: -1 / (2 * s)},
                        lo_z=1, hi_z=1, edge=[])
            if op.edge:
                spec["edge"] = [
                    (("lo", 0), ("lo", 1), 1 / s),
                    (("lo", 0), ("lo", 0), -1 / s),
                    (("hi", 0), ("hi", 0), 1 / s),
                    (("hi", 0), ("hi", 1), -1 / s)]
            return spec
        c = 1 / (12 * s)  # centered 5-point
        spec = dict(w=2, taps={-2: c, -1: -8 * c, 1: 8 * c, 2: -c},
                    lo_z=2, hi_z=2, edge=[])
        if op.edge:
            spec["edge"] = [
                (("lo", 0), ("lo", 1), 1 / s),
                (("lo", 0), ("lo", 0), -1 / s),
                (("lo", 1), ("lo", 2), 1 / (2 * s)),
                (("lo", 1), ("lo", 0), -1 / (2 * s)),
                (("hi", 1), ("hi", 0), 1 / (2 * s)),
                (("hi", 1), ("hi", 2), -1 / (2 * s)),
                (("hi", 0), ("hi", 0), 1 / s),
                (("hi", 0), ("hi", 1), -1 / s)]
        return spec
    if isinstance(op, _LocalSecond):
        s2 = s * s
        if op.kind == "forward":
            return dict(w=2, taps={0: 1 / s2, 1: -2 / s2, 2: 1 / s2},
                        lo_z=0, hi_z=2, edge=[])
        if op.kind == "backward":
            return dict(w=2, taps={0: 1 / s2, -1: -2 / s2, -2: 1 / s2},
                        lo_z=2, hi_z=0, edge=[])
        spec = dict(w=1, taps={-1: 1 / s2, 0: -2 / s2, 1: 1 / s2},
                    lo_z=1, hi_z=1, edge=[])
        if op.edge:
            spec["edge"] = [
                (("lo", 0), ("lo", 0), 1 / s2),
                (("lo", 0), ("lo", 1), -2 / s2),
                (("lo", 0), ("lo", 2), 1 / s2),
                (("hi", 0), ("hi", 2), 1 / s2),
                (("hi", 0), ("hi", 1), -2 / s2),
                (("hi", 0), ("hi", 0), 1 / s2)]
        return spec
    return None


class _StencilOperator(MPILinearOperator):
    """Common scaffolding: flat vector in → N-D stencil → flat vector out,
    with the reference's BROADCAST→SCATTER input conversion
    (ref ``FirstDerivative.py:128-132``) and axis-0 row-sharded output.

    ``overlap`` (``PYLOPS_MPI_TPU_OVERLAP``) selects the
    compute/comm-overlapped form of the explicit stencil kernel: the
    ghost ``ppermute``\\ s are issued first and consumed ONLY by the
    ``w``-row boundary patches, so the interior stencil — the bulk of
    the FLOPs — carries no dependence on the exchange and runs while
    the slabs fly (round 8; see :meth:`_apply_explicit`).

    ``hierarchical`` (``PYLOPS_MPI_TPU_HIERARCHICAL``, round 11): on a
    hybrid mesh (``make_mesh_hybrid``) the explicit stencil kernels are
    normally unavailable (they index a flat rank grid over ONE mesh
    axis) and the operator silently takes the implicit GSPMD path. With
    hierarchical enabled the kernels run over the axis TUPLE instead —
    the rank is linearized row-major across the axes, each ghost
    exchange stays the same single neighbour ``ppermute`` (already
    staged: only the slice-boundary pair crosses DCN), and the ghost
    byte counters split per fabric via ``topology.slice_map``. Results
    are bit-identical to the flat-mesh kernels (pure data movement plus
    the same local stencil); ``off`` keeps the implicit fallback."""

    def __init__(self, dims, mesh=None, dtype=None, overlap=None,
                 hierarchical=None):
        from ..utils.deps import overlap_enabled, hierarchical_enabled
        self.dims_nd = _tuplize(dims)
        n = int(np.prod(self.dims_nd))
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        # autotuner seam (round 10): the ghost strategy (bulk
        # halo-extend vs interior/boundary split) for a None overlap
        # comes from the plan when PYLOPS_MPI_TPU_TUNE=on|auto;
        # explicit kwargs/env pins always win, off is bit-identical
        from ..utils.deps import overlap_env_pinned
        if overlap is None and not overlap_env_pinned():
            from ..tuning import plan as _tuneplan
            tplan = _tuneplan.get_plan("derivative", shape=self.dims_nd,
                                       dtype=dtype, mesh=self.mesh)
            if tplan is not None \
                    and tplan.get("overlap") in ("on", "off"):
                overlap = tplan.get("overlap")
        self._overlap = overlap_enabled(overlap)
        # explicit-stencil mesh-axis handling (round 11): a single axis
        # name on a 1-D mesh; the full axis tuple (rank linearized
        # row-major) on a hybrid mesh with hierarchical enabled; None —
        # explicit path unavailable, implicit GSPMD fallback — on any
        # other multi-axis mesh (bit-identical to pre-round-11)
        from ..parallel import topology as _topo
        self._slice_map = _topo.slice_map(self.mesh)
        if len(self.mesh.axis_names) == 1:
            self._axes = self.mesh.axis_names[0]
        elif _topo.hybrid_axes(self.mesh) is not None \
                and hierarchical_enabled(hierarchical):
            self._axes = tuple(self.mesh.axis_names)
        else:
            self._axes = None
        # output local shapes: balanced row split of axis 0, flattened
        # (what the reference's @reshaped produces)
        rows = local_split(self.dims_nd, int(self.mesh.devices.size),
                           Partition.SCATTER, 0)
        self._out_locals = tuple((int(np.prod(s)),) for s in rows)
        self.dims = self.dimsd = self.dims_nd
        super().__init__(shape=(n, n), dtype=np.dtype(dtype or "float64"))

    def _local_op(self):
        raise NotImplementedError

    def _apply(self, x: DistributedArray, forward: bool) -> DistributedArray:
        if x.partition in (Partition.BROADCAST, Partition.UNSAFE_BROADCAST):
            x = x.to_partition(Partition.SCATTER)
        y = self._apply_explicit(x, forward)
        if y is not None:
            return y
        g = x.array.reshape(self.dims_nd)
        op = self._local_op()
        arr = op._matvec(g.ravel()) if forward else op._rmatvec(g.ravel())
        y = DistributedArray(global_shape=self.shape[0], mesh=self.mesh,
                             partition=Partition.SCATTER, axis=0,
                             local_shapes=self._out_locals, mask=x.mask,
                             dtype=arr.dtype)
        y[:] = arr
        return y

    def _apply_explicit(self, x: DistributedArray,
                        forward: bool) -> Optional[DistributedArray]:
        """Hand-scheduled stencil path: ONE shard_map kernel with a
        single ``ppermute`` pair exchanging only the ``w`` boundary rows
        (:func:`~pylops_mpi_tpu.parallel.collectives.cart_halo_extend`)
        — the explicit form of the ghost-cell schedule the reference
        hand-codes with Send/Recv (ref ``FirstDerivative.py:141-318``,
        ``SecondDerivative.py:215-240``, ``DistributedArray.py:877-954``).

        Covers every kind (forward/backward/centered), order (3/5),
        ``edge`` flag, and ragged (pad-to-max) balanced splits, via the
        ``y = Z·Sx + Ex`` decomposition of :func:`_stencil_spec`; the
        adjoint is the same kernel with reversed taps, input-side zero
        mask, and transposed edge triples. Centered-3 cores use the
        fused Pallas VMEM pass on TPU. Returns ``None`` (generic
        implicit GSPMD path) for non-axis-0 stencils, multi-dim meshes,
        non-balanced layouts, or shards shorter than the halo/edge
        span. Disable with ``PYLOPS_MPI_TPU_EXPLICIT_STENCIL=0``."""
        from ..utils import deps
        if not deps.explicit_stencil_enabled():
            return None
        op = self._local_op()
        if getattr(op, "axis", None) != 0:
            return None
        spec = _stencil_spec(op)
        if spec is None:
            return None
        if self._axes is None:  # multi-axis mesh, no hierarchical route
            return None
        P_ = int(self.mesh.devices.size)
        dims = self.dims_nd
        rows_tab = [int(s[0]) for s in
                    local_split(dims, P_, Partition.SCATTER, 0)]
        w = spec["w"]
        # every shard must hold the halo slab (ghosts come from the
        # immediate neighbour only); with edge corrections the boundary
        # shards must additionally hold the 3-row span they read locally
        min_rows = max(w, 3) if spec["edge"] else w
        if (x.partition != Partition.SCATTER or x.axis != 0 or x.ndim != 1
                or min(rows_tab) < min_rows
                or not jnp.issubdtype(x.dtype, jnp.floating)):
            return None
        inner = int(np.prod(dims[1:])) if len(dims) > 1 else 1
        if x._axis_sizes != tuple(r * inner for r in rows_tab):
            return None  # bespoke layout: implicit path handles it
        from jax import shard_map
        from jax import lax
        from jax.sharding import PartitionSpec as PSpec
        from ..parallel.collectives import halo_slab
        from .pallas_kernels import stencil_taps

        rmax = max(rows_tab)
        ragged = len(set(rows_tab)) > 1
        axis_name = self._axes
        slice_map = self._slice_map
        # linearized rank inside the kernel: plain axis_index on a 1-D
        # mesh, explicit row-major combination on a hybrid axis tuple
        # (the tuple form of lax.axis_index is not relied on)
        mesh_shape = np.asarray(self.mesh.devices).shape

        def flat_rank():
            if isinstance(axis_name, str):
                return lax.axis_index(axis_name)
            r = lax.axis_index(axis_name[0])
            for nm, sz in zip(axis_name[1:], mesh_shape[1:]):
                r = r * int(sz) + lax.axis_index(nm)
            return r
        n0 = dims[0]
        lo_z, hi_z = spec["lo_z"], spec["hi_z"]
        taps = (spec["taps"] if forward
                else {-d: c for d, c in spec["taps"].items()})
        triples = (spec["edge"] if forward
                   else [(i, o, c) for (o, i, c) in spec["edge"]])
        import jax as _jax
        on_tpu = _jax.default_backend() == "tpu"
        # any tap set runs as a fused Pallas VMEM pass on TPU, tiled
        # over the column (lane) axis for wide shards; stencil_taps
        # itself falls back to the identical jnp slice form for shapes
        # it cannot tile, so no external size gate is needed
        pallas_core = None
        if on_tpu:
            taps_t = tuple(sorted(taps.items()))

            def pallas_core(slab, _t=taps_t):
                # stencil_taps flattens/restores trailing dims itself
                return stencil_taps(slab, _t, w)
        valid_tab = jnp.asarray(rows_tab, dtype=jnp.int32)
        base_tab = jnp.asarray(np.concatenate([[0], np.cumsum(rows_tab)[:-1]]),
                               dtype=jnp.int32)
        # compute/comm overlap (round 8): split the stencil into the
        # interior (needs no ghosts — the bulk of the work) and the two
        # w-row boundary patches (the only consumers of the ppermuted
        # slabs), so the exchange flies while the interior computes.
        # Requires every shard to hold the 2w rows each patch reads
        # locally; shorter shards keep the bulk ghosted-slab kernel.
        use_overlap = (self._overlap and P_ > 1 and w > 0
                       and min(rows_tab) >= 2 * w)

        def kernel(xb):
            b = xb.reshape((rmax,) + tuple(dims[1:]))
            idx = flat_rank()
            valid = jnp.take(valid_tab, idx)
            row = lax.broadcasted_iota(jnp.int32, b.shape, 0)
            G = jnp.take(base_tab, idx) + row  # global row index
            zero = jnp.zeros((), b.dtype)
            if ragged:  # scrub pad-tail garbage before it is exchanged
                b = jnp.where(row < valid, b, zero)
            b_orig = b  # edge corrections read the unmasked input
            if not forward:  # (Z·S)ᴴ = Sᵀ·Z: zero the masked input rows
                zin = (G < lo_z) | (G > n0 - 1 - hi_z)
                b = jnp.where(zin, zero, b)
            if use_overlap:
                from ..parallel.collectives import ring_halo_ghosts
                # ghosts first: consumed only by the boundary patches
                gf, gb = ring_halo_ghosts(b, axis_name, P_, w, w, valid,
                                          slice_map=slice_map)
                # interior: the zero-extended local slab — exact
                # everywhere except the first/last w valid rows
                padw = [(w, w)] + [(0, 0)] * (b.ndim - 1)
                zslab = jnp.pad(b, padw)
                if pallas_core is not None:
                    y = pallas_core(zslab)
                else:
                    y = sum(c * lax.slice_in_dim(zslab, w + d,
                                                 w + d + rmax, axis=0)
                            for d, c in taps.items())

                def tap_rows(sl, nrows):
                    return sum(c * lax.slice_in_dim(sl, w + d,
                                                    w + d + nrows,
                                                    axis=0)
                               for d, c in taps.items())

                # patch rows [0, w): slab rows [0, 3w) = [gf; b[:2w]]
                top_in = jnp.concatenate(
                    [gf, lax.slice_in_dim(b, 0, 2 * w, axis=0)], axis=0)
                y = jnp.concatenate(
                    [tap_rows(top_in, w),
                     lax.slice_in_dim(y, w, rmax, axis=0)], axis=0)
                # patch rows [valid-w, valid): slab rows
                # [valid-2w, valid+w) = [b[valid-2w:valid]; gb]
                bot_in = jnp.concatenate(
                    [lax.dynamic_slice_in_dim(b, valid - 2 * w, 2 * w,
                                              axis=0), gb], axis=0)
                y = lax.dynamic_update_slice_in_dim(
                    y, tap_rows(bot_in, w), valid - w, axis=0)
            else:
                slab = halo_slab(b, axis_name, P_, 0, w, w, valid, rmax,
                                 ragged, slice_map=slice_map)
                if pallas_core is not None:
                    y = pallas_core(slab)
                else:
                    y = sum(c * lax.slice_in_dim(slab, w + d,
                                                 w + d + rmax, axis=0)
                            for d, c in taps.items())
            if forward and (lo_z or hi_z):
                y = jnp.where((G < lo_z) | (G > n0 - 1 - hi_z), zero, y)
            if triples:
                first3 = b_orig[0:3]  # global rows 0..2 on shard 0
                last3 = lax.dynamic_slice_in_dim(
                    b_orig, jnp.maximum(valid - 3, 0), 3, axis=0)
                for (oside, oi), (iside, ii), coef in triples:
                    orow = oi if oside == "lo" else n0 - 1 - oi
                    src = first3[ii] if iside == "lo" else last3[2 - ii]
                    # masks select shard 0 / shard P-1 rows only, so the
                    # other shards' (meaningless) src values are dropped
                    y = y + jnp.where(G == orow, coef * src[None], zero)
            if ragged:
                y = jnp.where(row < valid, y, zero)
            return y.reshape(-1)

        out = shard_map(kernel, mesh=self.mesh, in_specs=PSpec(axis_name),
                        out_specs=PSpec(axis_name), check_vma=False)(x._arr)
        y = DistributedArray(global_shape=self.shape[0], mesh=self.mesh,
                             partition=Partition.SCATTER, axis=0,
                             local_shapes=self._out_locals, mask=x.mask,
                             dtype=out.dtype)
        y._arr = y._place(out)
        return y

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, True)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, False)


class MPIFirstDerivative(_StencilOperator):
    """First derivative along axis 0
    (ref ``basicoperators/FirstDerivative.py:18-318``): forward /
    backward / centered stencils of order 3 or 5, with ``edge`` handling
    at the domain boundary (the reference special-cases rank 0 and rank
    P-1; here the boundary is just the edge of the global array)."""

    def __init__(self, dims, sampling: float = 1.0, kind: str = "centered",
                 edge: bool = False, order: int = 3, mesh=None,
                 dtype=np.float64, overlap=None, hierarchical=None):
        super().__init__(dims, mesh=mesh, dtype=dtype, overlap=overlap,
                         hierarchical=hierarchical)
        self.sampling = sampling
        self.kind = kind
        self.edge = edge
        self.order = order
        if kind not in ("forward", "backward", "centered"):
            raise NotImplementedError(
                "'kind' must be 'forward', 'centered', or 'backward'")
        self._op = _LocalFirst(self.dims_nd, axis=0, sampling=sampling,
                               kind=kind, edge=edge, order=order, dtype=dtype)

    def _local_op(self):
        return self._op


class MPISecondDerivative(_StencilOperator):
    """Second derivative along axis 0
    (ref ``basicoperators/SecondDerivative.py:13-256``): forward /
    backward / centered 3-point stencils; ``edge`` adds the one-sided
    boundary rows for centered (the reference special-cases rank 0 and
    rank P-1, ref ``SecondDerivative.py:215-240``; here the boundary is
    the edge of the global array)."""

    def __init__(self, dims, sampling: float = 1.0, kind: str = "centered",
                 edge: bool = False, mesh=None, dtype=np.float64,
                 overlap=None, hierarchical=None):
        super().__init__(dims, mesh=mesh, dtype=dtype, overlap=overlap,
                         hierarchical=hierarchical)
        self.sampling = sampling
        self.kind = kind
        self.edge = edge
        self._op = _LocalSecond(self.dims_nd, axis=0, sampling=sampling,
                                kind=kind, edge=edge, dtype=dtype)

    def _local_op(self):
        return self._op


class MPILaplacian(_StencilOperator):
    """Laplacian: weighted sum of second derivatives along ``axes``
    (ref ``basicoperators/Laplacian.py:15-126``, which routes the
    distributed axis through MPISecondDerivative and local axes through
    MPIBlockDiag).

    One operator, two forms, chosen from what the operator and its
    input show (:meth:`_kernel_refusal`; no keyword, no environment
    variable):

    * ``pmt_laplacian`` — forward and adjoint are each ONE Pallas pass
      over the shard's cube where it lies
      (``pallas_kernels.laplacian_stencil``: compiled on a TPU,
      interpreted elsewhere), inside a ``shard_map`` over the 1-D mesh
      with the two axis-0 ghost planes from
      ``collectives.ring_halo_ghosts`` as operands (zeros at the ends
      of the global array; one device sends nothing). Taken for a real
      floating dtype and real weights, ``kind="centered"``,
      ``edge=False``, 2-D or 3-D ``dims`` (2-D is a cube with a middle
      axis of one), every stencilled axis at least 3 long, a 1-D mesh,
      an input in the balanced, unpadded axis-0 split with a plane or
      more a shard and, where the kernel is compiled, f32 planes of
      whole ``(8, 128)`` tiles (``n1 % 8 == 0``, ``n2 % 128 == 0``) of
      at most 4 MiB. Live in an apply: the input and the output,
      **2 volumes**, and two ghost planes.
    * ``slices`` — everything else (``forward`` / ``backward`` kinds,
      ``edge=True``, complex, ragged shares, a hybrid mesh, a 1-D or
      4-D array): the sum of ``ops/local.py::SecondDerivative`` applies
      on the logical global array, XLA inserting the halo exchange for
      axis 0.

    ``laplacian.path_select`` (``form``, ``dims``, ``axes``,
    ``adjoint``, ``shards`` and, for ``slices``, a one-word ``why``)
    says what a traced apply took under ``PYLOPS_MPI_TPU_TRACE``."""

    def __init__(self, dims, axes=(-2, -1), weights=(1, 1), sampling=(1, 1),
                 kind: str = "centered", edge: bool = False, mesh=None,
                 dtype=np.float64):
        super().__init__(dims, mesh=mesh, dtype=dtype)
        axes = tuple(ax % len(self.dims_nd) for ax in axes)
        if not (len(axes) == len(weights) == len(sampling)):
            raise ValueError("axes, weights, and sampling have different size")
        self.axes, self.weights, self.sampling = axes, tuple(weights), tuple(sampling)
        self.kind, self.edge = kind, edge
        self._ops = [_LocalSecond(self.dims_nd, axis=ax, sampling=s,
                                  kind=kind, edge=edge, dtype=dtype)
                     for ax, s in zip(axes, sampling)]

    def _cube(self, rows: int) -> Tuple[int, int, int]:
        """A shard of ``rows`` planes as the kernel's cube: 2-D ``dims``
        get a middle axis of one."""
        return (rows,) + (1,) * (3 - len(self.dims_nd)) + self.dims_nd[1:]

    def _kernel_refusal(self, x: DistributedArray) -> Optional[str]:
        """``None`` where ``pmt_laplacian`` takes the apply, else the
        one word ``laplacian.path_select`` gives as ``why``."""
        from .pallas_kernels import laplacian_legal
        dims, P_ = self.dims_nd, int(self.mesh.devices.size)
        if self.kind != "centered":
            return "kind"
        if self.edge:
            return "edge"
        if not (jnp.issubdtype(x.dtype, jnp.floating)
                and all(np.isreal(w) for w in self.weights)):
            return "dtype"
        if not isinstance(self._axes, str):
            return "mesh"
        if len(dims) not in (2, 3) or any(dims[ax] < 3 for ax in self.axes):
            return "short"
        inner = int(np.prod(dims[1:]))
        if (dims[0] % P_ or x.partition != Partition.SCATTER or x.axis != 0
                or x.ndim != 1
                or x._axis_sizes != (dims[0] // P_ * inner,) * P_):
            return "ragged"
        if not laplacian_legal(self._cube(dims[0] // P_), x.dtype):
            return "align"
        return None

    def _apply(self, x: DistributedArray, forward: bool) -> DistributedArray:
        if x.partition in (Partition.BROADCAST, Partition.UNSAFE_BROADCAST):
            x = x.to_partition(Partition.SCATTER)
        why = self._kernel_refusal(x)
        _trace.event("laplacian.path_select", cat="schedule",
                     form="slices" if why else "pmt_laplacian",
                     dims=self.dims_nd, axes=self.axes,
                     adjoint=int(not forward),
                     shards=int(self.mesh.devices.size),
                     **({"why": why} if why else {}))
        if why is None:
            return DistributedArray._wrap(
                self._apply_kernel(x._arr, forward), x)
        g = x.array.ravel()
        if forward:
            arr = sum(w * op._matvec(g) for w, op in zip(self.weights, self._ops))
        else:
            arr = sum(np.conj(w) * op._rmatvec(g)
                      for w, op in zip(self.weights, self._ops))
        y = DistributedArray(global_shape=self.shape[0], mesh=self.mesh,
                             partition=Partition.SCATTER, axis=0,
                             local_shapes=self._out_locals, mask=x.mask,
                             dtype=arr.dtype)
        y[:] = arr
        return y

    def _apply_kernel(self, arr, forward: bool):
        """``pmt_laplacian`` on every shard of the flat physical array:
        a ``shard_map``, so GSPMD is never handed a custom call it
        cannot partition."""
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as PSpec
        from ..parallel.collectives import ring_halo_ghosts
        from .pallas_kernels import laplacian_stencil
        dims, P_ = self.dims_nd, int(self.mesh.devices.size)
        rows = dims[0] // P_
        cube = self._cube(rows)
        coef = [0.0, 0.0, 0.0]
        for ax, w, s in zip(self.axes, self.weights, self.sampling):
            # 2-D dims: axis 1 lies on the cube's lanes, axis 2
            cube_ax = ax if ax == 0 else ax + 3 - len(dims)
            coef[cube_ax] += float(np.real(w)) / float(s) ** 2
        axis_name, slice_map = self._axes, self._slice_map

        def kernel(xb):
            b = xb.reshape(cube)
            if coef[0]:
                gf, gb = ring_halo_ghosts(b, axis_name, P_, 1, 1, rows,
                                          slice_map=slice_map)
            else:
                gf = gb = jnp.zeros((1,) + cube[1:], b.dtype)
            base = lax.axis_index(axis_name) * rows
            return laplacian_stencil(b, gf, gb, base, dims[0], coef,
                                     adjoint=not forward).reshape(-1)

        return shard_map(kernel, mesh=self.mesh, in_specs=PSpec(axis_name),
                         out_specs=PSpec(axis_name), check_vma=False)(arr)


class MPIGradient(MPILinearOperator):
    """Gradient: vertical stack of first derivatives along every axis
    (ref ``basicoperators/Gradient.py:21-118``: MPIFirstDerivative for
    axis 0 + MPIBlockDiag(local FirstDerivative) for the others, stacked
    with MPIStackedVStack). Output is a StackedDistributedArray with one
    component per axis."""

    def __init__(self, dims, sampling=1, kind: str = "centered",
                 edge: bool = False, mesh=None, dtype=np.float64,
                 overlap=None, hierarchical=None):
        self.dims_nd = _tuplize(dims)
        ndims = len(self.dims_nd)
        # NOT _tuplize: sampling is a float spacing, an int cast would
        # truncate e.g. 0.5 -> 0 and blow up the stencils
        sampling = tuple(float(s) for s in np.atleast_1d(sampling))
        if len(sampling) == 1:
            sampling = sampling * ndims
        if len(sampling) != ndims:
            raise ValueError(
                f"sampling must have 1 or {ndims} entries, got {len(sampling)}")
        self.sampling = sampling
        self.kind = kind
        self.edge = edge
        grad_ops = []
        for ax in range(ndims):
            op = _AxisFirstDerivative(self.dims_nd, axis=ax,
                                      sampling=sampling[ax], kind=kind,
                                      edge=edge, mesh=mesh, dtype=dtype,
                                      overlap=overlap,
                                      hierarchical=hierarchical)
            grad_ops.append(op)
        stack = MPIStackedVStack(grad_ops)
        super().__init__(shape=stack.shape, dtype=np.dtype(dtype))
        self.Op = stack  # after super().__init__, which resets self.Op
        self.dims = self.dimsd = self.dims_nd

    def _matvec(self, x: DistributedArray) -> StackedDistributedArray:
        return self.Op._matvec(x)

    def _rmatvec(self, x: StackedDistributedArray) -> DistributedArray:
        return self.Op._rmatvec(x)


class _AxisFirstDerivative(_StencilOperator):
    """First derivative along an arbitrary axis of the axis-0-sharded
    layout (the reference expresses non-0 axes as rank-local pylops ops
    inside MPIBlockDiag, ref ``Gradient.py:88-97``)."""

    def __init__(self, dims, axis, sampling, kind, edge, mesh=None,
                 dtype=np.float64, overlap=None, hierarchical=None):
        super().__init__(dims, mesh=mesh, dtype=dtype, overlap=overlap,
                         hierarchical=hierarchical)
        self._op = _LocalFirst(self.dims_nd, axis=axis, sampling=sampling,
                               kind=kind, edge=edge, dtype=dtype)

    def _local_op(self):
        return self._op


# array-less pytree registration: lets stencil operators ride inside
# registered wrapper compositions passed into jit (linearoperator.py)
from ..linearoperator import register_operator_arrays  # noqa: E402
for _c in (MPIFirstDerivative, MPISecondDerivative, MPILaplacian,
           _AxisFirstDerivative):
    register_operator_arrays(_c)
register_operator_arrays(MPIGradient, "Op")
