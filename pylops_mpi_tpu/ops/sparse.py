"""Distributed sparse matrix–vector products (the sparse matmul tier).

The dense tier (:mod:`.matrixmult`) pays ``2·N·M`` flops and streams
``N·M`` matrix elements per apply regardless of structure.  Many of the
operators PyLops users feed through ``MatrixMult`` are sparse —
regularization stencils, picking/masking matrices, banded systems — and
at ≥90% sparsity the dense GEMM is pure waste: the MXU multiplies
zeros and HBM streams them.  :class:`MPISparseMatrixMult` stores only
the ``nnz`` nonzeros as flattened COO-of-CSR triplets and applies them
with gather + ``segment_sum`` (forward) / scatter-add (adjoint), so
both flops and bytes scale with ``nnz`` instead of ``N·M``.

Layout.  The triplets are kept **row-sorted** (CSR order): ``rows`` is
the nondecreasing row index of each nonzero, ``cols`` its column,
``data`` its value.  Row-sorted segments make ``segment_sum`` emit its
``indices_are_sorted`` fast path and keep each device's slice of the
flattened arrays contiguous in rows — the "row-sharded" layout of the
reference's distributed CSR, realized here as a sharding of the nnz
axis rather than per-rank Python state.

Adjoint.  Two schedules:

- ``"scatter"`` (default): one logical ``zeros(Ncol).at[cols].add``
  — XLA's SPMD partitioner lowers the scatter plus the implicit
  cross-shard reduction (one psum-shaped combine).  Fully fused, jit-
  and vmap-safe, the schedule the solver tier traces into its loops.
- ``"ring"``: an explicit ``shard_map`` kernel reusing
  :func:`~pylops_mpi_tpu.parallel.collectives.ring_pass` — each device
  owns an equal slice of the nnz triplets, the (values, cols) bundle
  rotates around the ring, and every device folds the resident slice's
  contributions into its own block of ``x``.  P−1 ppermutes interleave
  with P masked scatters, so the hop of slice ``s+1`` flies while
  slice ``s`` accumulates — the overlap path for adjoint-heavy solves
  (CGLS) on real ICI.  Ragged ``Ncol`` is ceil-padded per block and
  sliced off after the gather.

Both paths produce bit-identical results up to floating-point
reassociation of the cross-shard sum; tests pin scatter-vs-ring parity
to engine precision.

Tier selection.  ``auto_sparse_matmult`` consults the tuner
(``tuning.get_plan("sparse_matmult", ...)`` with ``nnz`` in the key)
and builds the sparse operator only when the cost seed — flops and
bytes ∝ nnz vs the dense ``N·M`` — says it wins; tuning off (the
default) always returns the dense operator, so the sparse-tier-off HLO
stays bit-identical to today (pinned).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..distributedarray import DistributedArray, Partition
from ..linearoperator import MPILinearOperator, register_operator_arrays

__all__ = ["MPISparseMatrixMult", "auto_sparse_matmult"]


class MPISparseMatrixMult(MPILinearOperator):
    """Row-sharded sparse (CSR/banded) matrix multiplication.

    Parameters
    ----------
    rows, cols : array-like (nnz,) int
        Row/column index of each nonzero. ``rows`` must be
        nondecreasing (CSR order); :meth:`from_dense` and
        :meth:`from_banded` produce it sorted.
    data : array-like (nnz,)
        Nonzero values.
    shape : (N, Ncol)
        Dense shape of the matrix.
    mesh : jax.sharding.Mesh, optional
        1-D device mesh (default: the process-wide default mesh).
    dtype, compute_dtype : optional
        Operator dtype and the dtype the gathered products are formed
        in (e.g. ``bfloat16`` values with ``float32`` accumulation).
    adjoint_mode : {"scatter", "ring"}
        Adjoint schedule (see module docstring).
    """

    accepts_block = True

    def __init__(self, rows, cols, data, shape: Tuple[int, int], *,
                 mesh=None, dtype=None, compute_dtype=None,
                 adjoint_mode: str = "scatter"):
        if adjoint_mode not in ("scatter", "ring"):
            raise ValueError(f"adjoint_mode={adjoint_mode!r} "
                             "(expected 'scatter' or 'ring')")
        rows = np.asarray(rows)
        if rows.size and np.any(np.diff(rows) < 0):
            order = np.argsort(rows, kind="stable")
            rows = rows[order]
            cols = np.asarray(cols)[order]
            data = np.asarray(data)[order]
        self._rows = jnp.asarray(rows, dtype=jnp.int32)
        self._cols = jnp.asarray(cols, dtype=jnp.int32)
        self._data = jnp.asarray(data)
        if dtype is not None:
            self._data = self._data.astype(dtype)
        self.N, self.Ncol = int(shape[0]), int(shape[1])
        self.nnz = int(self._rows.shape[0])
        if self.nnz:
            rmax = int(np.max(rows))
            cmax = int(np.max(np.asarray(cols)))
            if rmax >= self.N or cmax >= self.Ncol:
                raise ValueError(
                    f"triplet index ({rmax}, {cmax}) outside shape "
                    f"({self.N}, {self.Ncol})")
        self.compute_dtype = compute_dtype
        self.adjoint_mode = adjoint_mode
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        super().__init__(shape=(self.N, self.Ncol),
                         dtype=np.dtype(self._data.dtype))

    # ------------------------------------------------------- constructors
    @classmethod
    def from_dense(cls, A, *, tol: float = 0.0, **kw):
        """Build from a dense matrix, keeping entries with
        ``|a| > tol`` (row-major scan → CSR order for free)."""
        A = np.asarray(A)
        if A.ndim != 2:
            raise ValueError(f"from_dense expects 2-D, got {A.shape}")
        rows, cols = np.nonzero(np.abs(A) > tol)
        return cls(rows, cols, A[rows, cols], A.shape, **kw)

    @classmethod
    def from_banded(cls, offsets, bands, shape: Tuple[int, int], **kw):
        """Build from a banded description: for each diagonal
        ``offsets[k]``, ``bands[k]`` holds its entries (length of the
        diagonal within ``shape``; scipy ``dia``-style)."""
        N, Ncol = int(shape[0]), int(shape[1])
        rows_l, cols_l, data_l = [], [], []
        for off, band in zip(offsets, bands):
            off = int(off)
            r0, c0 = (max(0, -off), max(0, off))
            ln = min(N - r0, Ncol - c0)
            if ln <= 0:
                continue
            band = np.asarray(band)
            if band.shape[0] != ln:
                raise ValueError(
                    f"band at offset {off} has {band.shape[0]} entries; "
                    f"diagonal length is {ln}")
            rows_l.append(np.arange(r0, r0 + ln))
            cols_l.append(np.arange(c0, c0 + ln))
            data_l.append(band)
        if not rows_l:
            return cls(np.zeros(0, int), np.zeros(0, int),
                       np.zeros(0), shape, **kw)
        return cls(np.concatenate(rows_l), np.concatenate(cols_l),
                   np.concatenate(data_l), shape, **kw)

    # ------------------------------------------------------------ queries
    @property
    def density(self) -> float:
        return self.nnz / float(max(1, self.N * self.Ncol))

    def diagonal(self) -> jax.Array:
        """Main diagonal (length ``min(N, Ncol)``) — the Jacobi
        preconditioner's fast path (:mod:`.precond`)."""
        n = min(self.N, self.Ncol)
        d = jnp.zeros(n, dtype=self._data.dtype)
        on = self._rows == self._cols
        idx = jnp.where(on, self._rows, n)  # off-diagonal -> dropped
        return d.at[idx].add(jnp.where(on, self._data, 0),
                             mode="drop")

    def todense(self):
        A = jnp.zeros((self.N, self.Ncol), dtype=self._data.dtype)
        return A.at[self._rows, self._cols].add(self._data)

    # ------------------------------------------------------------- apply
    def _wdt(self, g):
        if self.compute_dtype is not None:
            return np.dtype(self.compute_dtype)
        return np.promote_types(g.dtype, self._data.dtype)

    def _wrap_out(self, arr: jax.Array, x: DistributedArray,
                  length: int) -> DistributedArray:
        gshape = (length,) if arr.ndim == 1 else (length, arr.shape[1])
        y = DistributedArray(global_shape=gshape, mesh=x.mesh,
                            partition=Partition.SCATTER, axis=0,
                            mask=x.mask, dtype=arr.dtype)
        y[:] = arr
        return y

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        g = x._global()
        wdt = self._wdt(g)
        vals = self._data.astype(wdt)
        xg = jnp.take(g, self._cols, axis=0).astype(wdt)
        prod = vals[:, None] * xg if g.ndim == 2 else vals * xg
        y = jax.ops.segment_sum(prod, self._rows,
                                num_segments=self.N,
                                indices_are_sorted=True)
        return self._wrap_out(y.astype(self.dtype), x, self.N)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        g = x._global()
        wdt = self._wdt(g)
        vals = jnp.conj(self._data).astype(wdt)
        yg = jnp.take(g, self._rows, axis=0).astype(wdt)
        prod = vals[:, None] * yg if g.ndim == 2 else vals * yg
        if (self.adjoint_mode == "ring" and g.ndim == 1
                and len(self.mesh.axis_names) == 1):
            out = self._rmatvec_ring(prod)
        else:
            shp = (self.Ncol,) if g.ndim == 1 else (self.Ncol,
                                                    g.shape[1])
            out = jnp.zeros(shp, dtype=wdt).at[self._cols].add(prod)
        return self._wrap_out(out.astype(self.dtype), x, self.Ncol)

    def _rmatvec_ring(self, prod: jax.Array) -> jax.Array:
        """Explicit ring adjoint: rotate the (values, cols) bundle,
        fold the resident slice into this device's x-block."""
        from jax import shard_map
        from ..parallel.collectives import ring_pass
        from jax.sharding import PartitionSpec as PSpec

        P_ = int(self.mesh.devices.size)
        name = self.mesh.axis_names[0]
        if P_ == 1:
            return jnp.zeros(self.Ncol, dtype=prod.dtype) \
                      .at[self._cols].add(prod)
        npad = P_ * (-(-self.nnz // P_))       # nnz ceil-padded
        cw = -(-self.Ncol // P_)               # x-block width
        # padding scatters value 0 to column 0 of block 0 — harmless
        vp = jnp.pad(prod, (0, npad - self.nnz))
        cp = jnp.pad(self._cols, (0, npad - self.nnz))

        def kernel(vl, cl):
            i = lax.axis_index(name)
            lo = i * cw

            def body(acc, resident, owner, s):
                v, c = resident
                loc = c - lo
                sel = (loc >= 0) & (loc < cw)
                return acc.at[jnp.where(sel, loc, cw)].add(
                    jnp.where(sel, v, 0), mode="drop")

            acc = ring_pass((vl, cl), name, P_, body,
                            init=jnp.zeros(cw, dtype=vl.dtype))
            return lax.all_gather(acc, name, axis=0, tiled=True)

        full = shard_map(kernel, mesh=self.mesh,
                         in_specs=(PSpec(name), PSpec(name)),
                         out_specs=PSpec(None), check_vma=False)(vp, cp)
        return full[:self.Ncol]


# Autodiff tier: ``_data`` (COO values) is the differentiable leaf —
# adjoint rules and implicit solver VJPs deliver value cotangents there.
# ``_rows``/``_cols`` are integer structure: their cotangents are float0
# (symbolic zeros), i.e. the sparsity PATTERN is not trainable.
register_operator_arrays(MPISparseMatrixMult, "_data", "_rows", "_cols")


def auto_sparse_matmult(A, *, mesh=None, dtype=None,
                        compute_dtype=None, tol: float = 0.0,
                        nnz: Optional[int] = None) -> MPILinearOperator:
    """Dense-or-sparse matmul tier selection through the tuner.

    Counts ``A``'s nonzeros and asks ``tuning.get_plan`` (space
    ``"sparse_matmult"``, cost ∝ nnz vs ``N·M``) which tier to build.
    With tuning off — the default — the plan is ``None`` and the dense
    operator is returned unconditionally, so existing programs lower
    to bit-identical HLO (pinned by tests/test_sparse.py).
    """
    A = np.asarray(A)
    if A.ndim != 2:
        raise ValueError(f"auto_sparse_matmult expects 2-D, got {A.shape}")
    N, Ncol = A.shape
    if nnz is None:
        nnz = int(np.count_nonzero(np.abs(A) > tol))

    tier = "dense"
    from ..tuning import plan as _tuneplan
    pl = _tuneplan.get_plan(
        "sparse_matmult", shape=(int(N), int(Ncol)),
        dtype=dtype if dtype is not None else A.dtype, mesh=mesh,
        extra={"nnz": int(nnz),
               "itemsize": int(np.dtype(dtype or A.dtype).itemsize)})
    if pl is not None:
        tier = pl.params.get("tier", "dense")
    if tier == "sparse":
        return MPISparseMatrixMult.from_dense(
            A, tol=tol, mesh=mesh, dtype=dtype,
            compute_dtype=compute_dtype)
    from .matrixmult import MPIMatrixMult
    return MPIMatrixMult(A, 1, mesh=mesh, dtype=dtype,
                         compute_dtype=compute_dtype)
