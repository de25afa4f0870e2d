"""Fredholm integral of the first kind, distributed over slices.

Rebuild of ``pylops_mpi/signalprocessing/Fredholm1.py:14-169``: batched
per-slice matmul ``d[k] = G[k] @ m[k]`` with the kernel ``G`` sharded
along its first (slice/frequency) dimension and BROADCAST model/data —
the reference computes each rank's slice batch then allgather+vstacks
the full data (ref ``129-131``).

TPU-native: one batched einsum with ``G`` slice-sharded. XLA shards the
batch dimension (each device contracts its own frequency batch on the
MXU) and replicates the result for the BROADCAST output — the same
gather, scheduled by the partitioner over ICI.

Beyond the reference (SURVEY §7.10): SCATTER model/data are also
accepted when the slice count divides the mesh. Each device then holds
only its frequency batch of the model AND the data, the einsum is
slice-aligned with ``G``'s sharding, and the whole apply contains ZERO
collectives — 1/P the memory of the reference's replicated-model
design. Construct the vectors with ``model_local_shapes`` /
``data_local_shapes``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..distributedarray import DistributedArray, Partition
from ..linearoperator import MPILinearOperator
from ..parallel.mesh import axis_sharding

__all__ = ["MPIFredholm1"]

# forward / adjoint contraction of a real kernel, and of the plane pair
# (batched over the pair): the adjoint contracts the OTHER axis of the
# stored kernel, so nothing of its size is transposed, conjugated or kept
_SPECS = {False: ("kxy,kyz->kxz", "pkxy,pkyz->pkxz"),
          True: ("kxy,kxz->kyz", "pkxy,pkxz->pkyz")}


def _real_dtype(dtype) -> np.dtype:
    """The real counterpart of a (complex) dtype."""
    return np.real(np.ones(1, dtype=np.dtype(dtype))).dtype


class MPIFredholm1(MPILinearOperator):
    """Distributed Fredholm1 (ref ``Fredholm1.py:14-169``).

    Parameters mirror the reference except ``G`` is the full global
    kernel ``(nsl, nx, ny)`` (one controller), not this rank's chunk.
    ``usematmul`` is accepted for signature parity but has no effect:
    it selects between per-slice matmul and einsum execution in the
    reference (identical results, ref ``Fredholm1.py:120-131``); here
    the batched einsum on the MXU is always the right schedule.

    **The kernel is held ONCE, and a complex kernel as its planes.**
    A complex ``G`` is stored as a real plane pair ``(2, nsl, nx, ny)``
    (``[0]`` real, ``[1]`` imaginary, the slice axis still sharded) on
    every backend, and may be GIVEN as that pair — a real 4-D array
    with a leading axis of two — which, when it is a device array of
    the stored dtype lying as the mesh wants it, is kept as itself (no
    copy). Why planes: a TPU holds no complex array natively; XLA
    splits a complex64 program argument into two float32 arrays at
    every program's entry (``X64SplitLow``/``High``), so a complex
    kernel of 8.59 GB is 8.59 GB of temporaries beside itself in every
    program that touches it (compiled for a v5e: 16.44 of 15.75 GB, it
    does not fit), and the complex ``einsum`` XLA then builds
    (three real products, Gauss) reads each plane twice an apply. On
    the planes an apply is one real ``einsum`` batched over the pair,
    the vector's parts side by side as columns — ``Gr·[vr|vi]`` and
    ``Gi·[vi|vr]`` — so each plane, and so the kernel's 8 bytes an
    element, is read ONCE an apply (PERF.md section 6, PR 34). A
    complex ``G`` handed in as such is split once at construction (a
    host array on the host; a device array by one transient copy of
    its size — give the planes where that does not fit).

    ``saveGt`` is, like ``usematmul``, accepted for signature parity
    and has no effect: the reference stores
    ``conj(G.transpose(0, 2, 1))`` beside ``G`` to spare its per-slice
    matmul a strided read; here the adjoint contracts the OTHER axis of
    the stored planes (``kxy,kxz->kyz``; ``Gᴴ = Grᵀ − i·Giᵀ``), so no
    second array of the kernel's size exists at construction or inside
    an apply.

    ``compute_dtype`` (e.g. ``jnp.complex64`` for a c128 operator,
    ``jnp.bfloat16`` for a real one) narrows the STORAGE of the
    kernel — by far the memory hog at ``nsl·nx·ny`` — while vectors
    and accumulation stay in the operator dtype (the
    ``MPIBlockDiag(compute_dtype=...)`` HBM-bandwidth lever; the
    reference's engine has no narrow-storage path); for a plane pair a
    complex ``compute_dtype`` means its real counterpart.

    ``planar=True``: the complex-free execution mode for TPU runtimes
    with no complex lowering (round-5 hardware finding, ops/dft.py):
    the VECTORS too are plane pairs — model/data carry the
    ``(2, nsl, ·, nz)`` layout and the operator dtype is the real plane
    dtype, so no complex dtype ever reaches the device. This is the
    Fredholm core of the planar MDC chain (``ops/mdc.py``); only
    BROADCAST vectors are supported (the zero-collective slice-aligned
    SCATTER layout is a flat-vector contract that the leading plane
    axis breaks).
    """

    def __init__(self, G, nz: int = 1, saveGt: bool = False,
                 usematmul: bool = True, mesh=None, dtype="float64",
                 compute_dtype=None, planar: bool = False):
        if not isinstance(G, jax.Array):
            G = np.asarray(G)          # a host kernel is split on the host
        xp = jnp if isinstance(G, jax.Array) else np
        self.planar = bool(planar)
        cplx = bool(np.issubdtype(G.dtype, np.complexfloating))
        if G.ndim == 4 and (cplx or G.shape[0] != 2):
            raise ValueError("a 4-D G is the complex kernel's real plane "
                             f"pair (2, nsl, nx, ny); got {G.dtype} "
                             f"{tuple(G.shape)}")
        # the kernel is a plane pair: given as one, complex, or planar
        self._planes = G.ndim == 4 or cplx or self.planar
        if self.planar and np.issubdtype(np.dtype(dtype),
                                         np.complexfloating):
            dtype = _real_dtype(dtype)
        elif self._planes and not self.planar:
            dtype = np.result_type(dtype, np.complex64)   # complex vectors
        if compute_dtype is None:
            # env-policy default: bf16 storage for f32 kernels under
            # the bf16 policy, c64 for c128 under the c64 policy
            from ._precision import default_compute_dtype
            compute_dtype = default_compute_dtype(dtype)
        if self._planes and compute_dtype is not None and \
                np.issubdtype(np.dtype(compute_dtype), np.complexfloating):
            # planes store the REAL representation
            compute_dtype = _real_dtype(compute_dtype)
        self.compute_dtype = compute_dtype
        self.nz = int(nz)
        if self._planes and G.ndim == 3:
            pdt = _real_dtype(G.dtype)
            G = xp.stack([xp.real(G).astype(pdt), xp.imag(G).astype(pdt)])
        self.nsl, self.nx, self.ny = G.shape[-3:]
        if compute_dtype is not None:
            G = G.astype(compute_dtype)
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        # the reference forbids shards with < 2 slices
        # (ref Fredholm1.py:79-83) — an artifact of its per-rank batched
        # matmul; the batched einsum here has no such limit, so any
        # nsl >= 1 is accepted
        if self.nsl < 1:
            raise ValueError("G must have at least one slice")
        plead = (2,) if self.planar else ()
        self.dims = plead + (self.nsl, self.ny, self.nz)
        self.dimsd = plead + (self.nsl, self.nx, self.nz)
        super().__init__(shape=(int(np.prod(self.dimsd)),
                                int(np.prod(self.dims))),
                         dtype=np.dtype(dtype))
        try:
            # a device array that already lies so becomes the storage
            self.G = jax.device_put(
                G, axis_sharding(self.mesh, G.ndim, G.ndim - 3),
                may_alias=True)
        except ValueError:
            self.G = jnp.asarray(G)
        self._ndev = int(self.mesh.devices.size)

    @property
    def model_local_shapes(self):
        """Slice-aligned SCATTER split of the flat model vector (the
        zero-communication layout); None when slices do not divide the
        mesh."""
        return self._slice_shapes(self.ny)

    @property
    def data_local_shapes(self):
        """Slice-aligned SCATTER split of the flat data vector."""
        return self._slice_shapes(self.nx)

    def _slice_shapes(self, inner):
        if self.planar or self.nsl % self._ndev != 0:
            # must match G's even NamedSharding for the zero-comm path
            # (planar: the leading plane axis breaks the flat
            # slice-aligned layout — BROADCAST only)
            return None
        from ..parallel.partition import flat_outer_shapes
        return flat_outer_shapes(self.nsl, inner * self.nz, self._ndev)

    def _check_partition(self, x, inner):
        if x.partition in (Partition.BROADCAST,
                           Partition.UNSAFE_BROADCAST):
            return
        shapes = self._slice_shapes(inner)
        if x.partition == Partition.SCATTER and shapes is not None \
                and tuple(x._axis_sizes) == tuple(s[0] for s in shapes):
            return
        raise ValueError(
            "x must be BROADCAST, or SCATTER with slice-aligned local "
            "shapes (model_local_shapes/data_local_shapes; requires "
            "nsl % n_devices == 0 and planar=False); got "
            f"{x.partition} with local sizes {tuple(x._axis_sizes)}")

    # block (column-batched) inputs fold their K columns into the
    # trailing z dimension of the SAME batched contraction (z -> z*K)
    accepts_block = True

    def _wrap(self, arr, x: DistributedArray, n: int,
              inner: int, ncol=None) -> DistributedArray:
        shapes = None
        if x.partition == Partition.SCATTER:
            shapes = self._slice_shapes(inner)
            if shapes is not None and ncol is not None:
                shapes = tuple(tuple(s) + (ncol,) for s in shapes)
        gshape = n if ncol is None else (n, ncol)
        y = DistributedArray(global_shape=gshape, mesh=x.mesh,
                             partition=x.partition, local_shapes=shapes,
                             dtype=self.dtype)
        y[:] = arr.ravel() if ncol is None else arr.reshape(-1, ncol)
        return y

    def _contract(self, spec, K, v):
        """Batched contraction honoring ``compute_dtype``: BOTH operands
        narrow, accumulation in the operator dtype (the shared
        narrow-storage rule, :mod:`ops._precision`)."""
        from ._precision import einsum_narrow
        out = _real_dtype(self.dtype) if self._planes \
            else np.result_type(v.dtype, self.dtype)
        if self.compute_dtype is None:
            v = v.astype(out)
        return einsum_narrow(spec, K, v, self.compute_dtype, out)

    def _contract_planes(self, vr, vi, adjoint: bool):
        """The complex product on the stored plane pair, each plane
        read ONCE, as one real ``einsum`` batched over the pair: the
        vector's parts side by side as columns, ``A = Gr·[vr|vi]`` and
        ``B = Gi·[vi|vr]``; forward ``(A₀ − B₀, A₁ + B₁)``, adjoint —
        ``Gᴴ = Grᵀ − i·Giᵀ``, contracting the other axis —
        ``(A₀ + B₀, A₁ − B₁)``. The stored array enters whole, never
        sliced: an apply outside a jit makes no copy of a plane either.
        Four real products where XLA's own complex ``dot`` makes three
        (Gauss) and reads each plane twice: the sweep binds here, not
        the flops."""
        nz = vr.shape[-1]
        V = jnp.stack([jnp.concatenate([vr, vi], -1),
                       jnp.concatenate([vi, vr], -1)])
        A, Bm = self._contract(_SPECS[adjoint][1], self.G, V)
        if adjoint:
            return A[..., :nz] + Bm[..., :nz], A[..., nz:] - Bm[..., nz:]
        return A[..., :nz] - Bm[..., :nz], A[..., nz:] + Bm[..., nz:]

    def _apply(self, x: DistributedArray, adjoint: bool) -> DistributedArray:
        inner, outer = (self.nx, self.ny) if adjoint else (self.ny, self.nx)
        dims = self.dimsd if adjoint else self.dims
        self._check_partition(x, inner)
        ncol = int(x.global_shape[1]) if x.ndim == 2 else None
        v = x.array.reshape(dims if ncol is None
                            else dims[:-1] + (self.nz * ncol,))
        if self.planar:
            out = jnp.stack(self._contract_planes(v[0], v[1], adjoint))
        elif self._planes:
            out = jax.lax.complex(*self._contract_planes(
                jnp.real(v), jnp.imag(v), adjoint))
        else:
            out = self._contract(_SPECS[adjoint][0], self.G, v)
        return self._wrap(out, x, self.shape[1 if adjoint else 0], outer,
                          ncol)

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, adjoint=False)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, adjoint=True)

    # ------------------------------------------- the plane-pair normal product
    def normal_form(self, columns: bool = False):
        """``(form, why, tile)`` of :meth:`normal_planes`: ``one_sweep``
        — the kernel ``pmt_normal_planes``, compiled, both products from
        one read of the planes — or ``pair``, the forward and the
        adjoint product, a plane ``einsum`` sweep each (the caller's to
        run), with the one word that says why: ``planar`` (no complex
        plane pair: a real kernel, or the planar engine), ``columns``
        (the vector has columns), ``interpret`` (no TPU: the kernel would
        be interpreted, a trap inside a solver's loop), ``tile`` (no
        Mosaic-legal row tile that the chip has shown to pay, a mesh
        that is not 1-D or slices that do not divide it), ``cols`` (more
        columns than the chip has shown to pay:
        ``pallas_kernels.plane_pair_cols_pay``). ``tile`` is the row tile
        the kernel takes, 0 where none is legal. A rule in what the
        operator stores and is handed; no keyword, no variable."""
        from . import pallas_kernels as pk
        tile = pk.plane_pair_tile(self.G) if self._planes else None
        if self.planar or not self._planes:
            why = "planar"
        elif columns:
            why = "columns"
        elif pk._interpret():
            why = "interpret"
        elif (tile is None or len(self.mesh.axis_names) != 1
              or self.nsl % self._ndev
              or not pk._tile_beats_two_sweeps(tile, self.ny,
                                               self.G.dtype.itemsize)):
            why = "tile"
        elif not pk.plane_pair_cols_pay(2 * self.nz):
            why = "cols"
        else:
            why = None
        return ("pair" if why else "one_sweep"), why, int(tile or 0)

    def normal_planes(self, v: DistributedArray, s: DistributedArray):
        """``(Q, Z1, Z2) = (G v, Gᴴ M Q, Gᴴ s)`` for a model-side
        spectrum ``v`` and a data-side one ``s`` (the operator's own
        vectors), ``M`` zeroing the imaginary part of slice 0: on
        ``MPIMDC``'s kept bins ``F1 F1ᴴ = M`` where they hold no Nyquist
        bin, so ``Z1`` is what ``F1ᴴ Q`` brought back through ``F1``
        would be. The kernel ``pmt_normal_planes`` makes all three from
        ONE read of the planes, each shard its own slices under
        ``shard_map`` (:meth:`normal_form` says where that pays). Under
        ``pmt.MPIFredholm1.normal_matvec``."""
        from ..diagnostics import trace
        with trace.op_span(self, "normal_matvec"):
            shape = lambda n: (self.nsl, n, self.nz)
            if self.planar:
                vr, vi = v.array.reshape((2,) + shape(self.ny))
                sr, si = s.array.reshape((2,) + shape(self.nx))
            else:
                vr, vi = (f(v.array.reshape(shape(self.ny)))
                          for f in (jnp.real, jnp.imag))
                sr, si = (f(s.array.reshape(shape(self.nx)))
                          for f in (jnp.real, jnp.imag))
            parts = self._normal_sweep(vr, vi, sr, si)
            out = []
            for (re, im), like, inner in zip(parts, (s, v, v),
                                             (self.nx, self.ny, self.ny)):
                arr = jnp.stack([re, im]) if self.planar \
                    else jax.lax.complex(re, im)
                out.append(self._wrap(arr, like, like.global_shape[0],
                                      inner))
            return tuple(out)

    def _normal_sweep(self, vr, vi, sr, si):
        """:meth:`normal_planes`' one sweep: the spectra's parts as rows
        (each part's columns padded to whole groups of
        ``PLANE_PAIR_ROWS``), the kernel per shard, the parts back in the
        operator's layout."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from . import pallas_kernels as pk
        nz = self.nz
        nzp = -(-nz // pk.PLANE_PAIR_ROWS) * pk.PLANE_PAIR_ROWS
        dt = _real_dtype(self.dtype)

        def rows(re, im):
            pad = ((0, 0), (0, 0), (0, nzp - nz))
            return jnp.swapaxes(jnp.concatenate(
                [jnp.pad(re, pad), jnp.pad(im, pad)], -1), 1, 2).astype(dt)
        axis = self.mesh.axis_names[0]
        nloc = self.nsl // self._ndev

        def kernel(G, C, S):
            first = jax.lax.axis_index(axis) * nloc
            return pk.plane_pair_normal(G, C, S, first)
        Q, Z = shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(None, axis), P(axis), P(axis)),
                         out_specs=(P(axis), P(axis)),
                         check_vma=False)(self.G, rows(vr, vi), rows(sr, si))
        cols = lambda A, k: jnp.swapaxes(A[:, k * nzp:k * nzp + nz], 1, 2)
        return ((cols(Q, 0), cols(Q, 1)), (cols(Z, 0), cols(Z, 2)),
                (cols(Z, 1), cols(Z, 3)))


# the frequency-sharded kernel travels into jit as a pytree child
# (multi-process arrays must not be closed over — linearoperator.py)
from ..linearoperator import register_operator_arrays  # noqa: E402
register_operator_arrays(MPIFredholm1, "G")
