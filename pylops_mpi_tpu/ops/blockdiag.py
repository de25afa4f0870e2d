"""Block-diagonal distributed operators.

Rebuild of ``pylops_mpi/basicoperators/BlockDiag.py:16-188``. In the
reference each MPI rank supplies its own list of local pylops operators
and applies them to its shard — embarrassingly parallel, no comm in
apply. Here the controller receives the *full* list of local operators,
assigns contiguous chunks to shards (one list per shard, exactly the
reference's layout), and the apply slices the sharded flat vector at
static offsets so XLA keeps each block's GEMM on the device owning it.

A fast path batches homogeneous blocks (same local shape) into a single
leading-axis-sharded ``vmap`` — one big MXU-friendly batched GEMM instead
of P small ones.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from ..distributedarray import DistributedArray, Partition
from ..stacked import StackedDistributedArray
from ..linearoperator import MPILinearOperator
from ..stackedlinearoperator import MPIStackedLinearOperator
from .local import LocalOperator, MatrixMult

__all__ = ["MPIBlockDiag", "MPIStackedBlockDiag"]


def _chunk_ops(ops: Sequence, n_shards: int) -> List[List]:
    """Assign operators to shards: contiguous balanced chunks (first
    ``len(ops) % P`` shards get one extra), mirroring the reference's
    one-list-per-rank layout under the balanced split rule."""
    n = len(ops)
    base, rem = divmod(n, n_shards)
    chunks, off = [], 0
    for i in range(n_shards):
        c = base + (1 if i < rem else 0)
        chunks.append(list(ops[off:off + c]))
        off += c
    return chunks


def _stacked_dims(dims: Sequence[tuple]) -> Optional[tuple]:
    """N-D shape of blocks laid one after another along their leading
    axis: ``(sum n_i,) + trailing`` where every block is N-D with the
    same trailing axes (post-stack modelling's ``(ny_i, nx, nt0)``
    blocks are the ``(ny, nx, nt0)`` cube), else ``None`` (flat)."""
    trailing = {tuple(d[1:]) for d in dims}
    if len(trailing) != 1 or any(len(d) < 2 for d in dims):
        return None
    return (int(sum(d[0] for d in dims)),) + tuple(
        int(n) for n in trailing.pop())


def _ncols(x: DistributedArray) -> int:
    """Columns of a vector (1) or of the block solvers' ``(rows, K)``
    vectors (K)."""
    return x.global_shape[1] if x.ndim == 2 else 1


class MPIBlockDiag(MPILinearOperator):
    """Distributed block-diagonal operator
    (ref ``basicoperators/BlockDiag.py:16-144``).

    Parameters
    ----------
    ops : list of LocalOperator
        All diagonal blocks (the concatenation of every rank's list in
        the reference API).
    mask : list of int, optional
        Shard-group coloring; carried onto input/output arrays so their
        reductions group exactly as the reference's sub-communicators do.
    compute_dtype : dtype, optional
        Narrow storage for the batched block stack (e.g.
        ``jnp.bfloat16``). When ``None``, the precision policy
        (``PYLOPS_MPI_TPU_PRECISION``, ops/_precision.py) decides —
        under the ``bf16`` policy f32 block stacks store narrow
        automatically; pass an explicit dtype to override either way.
    normal_path : str, optional
        Which ``normal_matvec`` implementation to use: ``"fused"``
        (the one-sweep Pallas kernel, when supported),
        ``"two_sweep"`` (plain matvec+rmatvec), or ``None``/``"auto"``
        (default) — fused when available, unless the autotuner
        (``PYLOPS_MPI_TPU_TUNE=on|auto``) has a measured plan saying
        otherwise. An explicit value always beats the tuner.
        ``cgls(normal=None)`` follows it through
        :meth:`prefers_fused_normal`: ``"two_sweep"`` (given or tuned)
        keeps the default solve classic; otherwise the solve takes the
        one-sweep schedule where the kernel is compiled (a TPU) with a
        row tile the chip has shown faster than two sweeps.

    ``dims`` / ``dimsd`` are metadata read from the blocks, no apply
    uses them: N-D blocks with equal trailing axes declare the cube
    they stack into (``(sum ny_i, nx, nt0)``), anything else — every
    ``MatrixMult`` — the flat ``(shape[1],)`` / ``(shape[0],)``. The
    fused solvers hold their carries in that shape
    (``solvers/basic.py::_carry_shape``).
    """

    def __init__(self, ops: Sequence[LocalOperator],
                 mask: Optional[Sequence[int]] = None,
                 mesh=None, dtype=None, compute_dtype=None,
                 normal_path: Optional[str] = None):
        if normal_path not in (None, "auto", "fused", "two_sweep"):
            raise ValueError(
                f"normal_path={normal_path!r}: expected None, 'auto', "
                "'fused' or 'two_sweep'")
        self.ops = list(ops)
        self.mask = tuple(mask) if mask is not None else None
        self.compute_dtype = compute_dtype
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        n_shards = int(self.mesh.devices.size)
        self.chunks = _chunk_ops(self.ops, n_shards)
        nops = np.asarray([op.shape[0] for op in self.ops])
        mops = np.asarray([op.shape[1] for op in self.ops])
        self.nops, self.mops = nops, mops
        # per-shard logical shapes (what the reference gathers at
        # construction, ref BlockDiag.py:106-120)
        self.local_shapes_n = tuple(
            (int(sum(op.shape[0] for op in c)),) for c in self.chunks)
        self.local_shapes_m = tuple(
            (int(sum(op.shape[1] for op in c)),) for c in self.chunks)
        shape = (int(nops.sum()), int(mops.sum()))
        dtype = dtype or np.result_type(*[op.dtype for op in self.ops])
        self.dims = _stacked_dims([op.dims for op in self.ops])
        self.dimsd = _stacked_dims([op.dimsd for op in self.ops])
        super().__init__(shape=shape, dtype=dtype)
        if self.compute_dtype is None:  # env-policy default (f32 only)
            from ._precision import default_compute_dtype
            self.compute_dtype = default_compute_dtype(dtype)
        self._batched = self._try_batch()
        # autotuner seam (round 10): the Pallas-vs-two-sweep
        # normal-equation path. Only consulted for the default
        # sentinel; PYLOPS_MPI_TPU_TUNE=off leaves _normal_path None
        # (= fused when available — exactly today's behavior).
        self._normal_path = None if normal_path == "auto" else normal_path
        if self._normal_path is None and self._batched is not None:
            from ..tuning import plan as _tuneplan
            nblk, m, n = self._batched.shape
            from ..utils.deps import batch_default
            tplan = _tuneplan.get_plan(
                "blockdiag", shape=self.shape, dtype=self.dtype,
                mesh=self.mesh,
                extra={"fused_available": bool(self.has_fused_normal),
                       "a_bytes": float(
                           nblk * m * n * self._batched.dtype.itemsize),
                       "batch": batch_default()})
            if tplan is not None \
                    and tplan.get("normal_path") in ("fused",
                                                     "two_sweep"):
                self._normal_path = tplan.get("normal_path")

    def _try_batch(self):
        """Homogeneous MatrixMult blocks → stacked batched GEMM, for
        plain (GEMV) blocks and uniform ``otherdims`` (multi-RHS GEMM)
        blocks alike — the latter is the GEMV→GEMM lever: one read of
        the stacked matrices feeds ``k`` columns on the MXU.

        ``compute_dtype`` (e.g. ``jnp.bfloat16``) re-stores the stacked
        blocks narrower — on TPU this halves the HBM traffic of the
        memory-bound matvec (the MXU accumulates in f32 regardless);
        vectors and reductions stay in the operator dtype."""
        self._batched_k = 1
        if not all(isinstance(op, MatrixMult) for op in self.ops):
            return None
        odims = {op.otherdims for op in self.ops}
        if len(odims) != 1:
            return None
        other = odims.pop()
        mats = [op.A_source for op in self.ops]
        shapes = {m.shape for m in mats}
        if len(shapes) != 1 or len(self.ops) % int(self.mesh.devices.size) != 0:
            return None
        self._batched_k = int(np.prod(other)) if other else 1
        from ._precision import check_compute_dtype
        check_compute_dtype(self.compute_dtype,
                            np.result_type(*{m.dtype for m in mats}),
                            "MPIBlockDiag")
        from ..parallel.mesh import stack_sharded
        return stack_sharded(mats, self.mesh, self.compute_dtype)

    # block (column-batched) inputs reuse the SAME batched einsum with a
    # widened trailing contraction — no per-column Python loop
    accepts_block = True

    def _apply(self, x: DistributedArray, forward: bool) -> DistributedArray:
        sizes_in = self.mops if forward else self.nops
        sizes_out = self.nops if forward else self.mops
        locals_out = self.local_shapes_n if forward else self.local_shapes_m
        y_shape = self.shape[0] if forward else self.shape[1]
        ncol = x.global_shape[1] if x.ndim == 2 else None
        if self._batched is not None:
            from ._precision import einsum_narrow
            A = self._batched
            nblk, m, n = A.shape
            k = self._batched_k
            nin = n if forward else m
            if ncol is None:
                X = x.array.reshape(nblk, nin, k)
            else:
                # K model columns fold into the existing GEMM columns:
                # the contraction widens from k to k*K, one einsum
                X = x.array.reshape(nblk, nin, k, ncol) \
                    .reshape(nblk, nin, k * ncol)
            if forward:
                Y = einsum_narrow("bmn,bnk->bmk", A, X,
                                  self.compute_dtype, self.dtype)
            else:
                Y = einsum_narrow("bnm,bnk->bmk", A.conj(), X,
                                  self.compute_dtype, self.dtype)
            nout = Y.shape[1]
            arr = (Y.ravel() if ncol is None
                   else Y.reshape(nblk, nout, k, ncol)
                   .reshape(y_shape, ncol))
        elif ncol is not None:
            # heterogeneous blocks: one compiled vmap over columns
            return self._apply_columns(x, forward)
        else:
            offs = np.concatenate([[0], np.cumsum(sizes_in)])
            parts = []
            for op, lo, hi in zip(self.ops, offs[:-1], offs[1:]):
                xb = x.array[int(lo):int(hi)]
                parts.append(op.matvec(xb) if forward else op.rmatvec(xb))
            arr = jnp.concatenate(parts)
        if ncol is not None:
            y_shape = (y_shape, ncol)
            locals_out = tuple(tuple(s) + (ncol,) for s in locals_out)
        y = DistributedArray(global_shape=y_shape, mesh=self.mesh,
                             partition=x.partition, axis=0,
                             local_shapes=locals_out, mask=self.mask,
                             dtype=arr.dtype)
        y[:] = arr
        return y

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, forward=True)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        return self._apply(x, forward=False)

    def diagonal(self) -> jnp.ndarray:
        """Concatenated main diagonals of the blocks — the Jacobi
        preconditioner's fast path (``ops/precond.probe_diagonal``
        resolves this before probing). Batched blocks read the stacked
        ``(nblk, m, n)`` array; heterogeneous stacks fall back to
        per-block ``jnp.diagonal`` of the local matrices."""
        if self._batched is not None and self._batched_k == 1:
            B = self._batched
            m = min(int(B.shape[1]), int(B.shape[2]))
            d = B[:, jnp.arange(m), jnp.arange(m)]
            return d.reshape(-1).astype(self.dtype)
        parts = []
        for op in self.ops:
            A = getattr(op, "A", None)
            if A is None:
                raise AttributeError(
                    "diagonal() needs matrix blocks (op.A); got "
                    f"{type(op).__name__}")
            parts.append(jnp.diagonal(jnp.asarray(A)))
        return jnp.concatenate(parts).astype(self.dtype)

    @property
    def has_fused_normal(self) -> bool:
        """A one-sweep Pallas kernel exists for these blocks — the same
        answer on every backend (compiled on a TPU, interpreted
        elsewhere): batched plain (``otherdims``-free) blocks on a 1-D
        mesh, real with a Mosaic-legal row tile, not forced to two
        sweeps (kwarg or tuned plan). Complex blocks answer no."""
        from .pallas_kernels import normal_matvec_supported
        return (self._normal_path != "two_sweep"
                and self._batched is not None
                and self._batched_k == 1  # columns come from x alone
                and len(self.mesh.axis_names) == 1  # shard_map is 1-D
                and normal_matvec_supported(self._batched))

    def _normal_kernel_for(self, x: DistributedArray):
        """*Can*: the one-sweep kernel ``normal_matvec(x)`` runs, or
        ``None`` when it takes the generic two sweeps. The kernel
        carries K columns a block and reads K from the input: a vector
        is K = 1, the block solvers' ``(rows, K)`` vectors bring their
        column axis (as many as fit VMEM beside the tile). It is real:
        a complex vector would be silently truncated."""
        from .pallas_kernels import (batched_normal_matvec,
                                     normal_matvec_supported)
        if (not self.has_fused_normal
                or jnp.issubdtype(x.dtype, jnp.complexfloating)
                or not normal_matvec_supported(self._batched, _ncols(x))):
            return None
        return batched_normal_matvec

    def prefers_fused_normal(self, x) -> bool:
        """*Pays*: ``normal_matvec(x)`` is the kernel, compiled (Pallas
        in interpret mode answers no: off a TPU the one-sweep path
        stays an explicit ``normal=True``), fed vectors of its
        accumulation dtype, with a row tile and a column count at which
        the chip has shown one sweep faster than two
        (``pallas_kernels.normal_matvec_pays``)."""
        from .pallas_kernels import normal_matvec_pays
        if self._normal_kernel_for(x) is None:
            return False
        acc = jnp.promote_types(self._batched.dtype, jnp.float32)
        return (np.dtype(x.dtype) == np.dtype(acc)
                and normal_matvec_pays(self._batched, _ncols(x)))

    def normal_matvec(self, x: DistributedArray):
        """``(u, q) = (OpᴴOp x, Op x)`` with ONE memory sweep of the
        block matrices where :attr:`has_fused_normal` and ``x`` is
        real, a vector or ``(rows, K)`` columns: the Pallas kernel
        feeds both products, all columns, from each VMEM-resident A
        tile (``pmt_normal``; interpreted off a TPU). Falls back to
        matvec+rmatvec otherwise."""
        kernel = self._normal_kernel_for(x)
        if kernel is None:
            return super().normal_matvec(x)
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from ..diagnostics import trace
        with trace.op_span(self, "normal_matvec"):
            A = self._batched
            nblk, m, n = A.shape
            tail = x.global_shape[1:]     # () for a vector, (K,) else
            # the kernel wants the long axis on lanes, (nblk, K, n): a
            # transpose at its edge for the solvers' column-minor
            # (rows, K) vectors, nothing for a vector
            X = jnp.swapaxes(x.array.reshape(nblk, n, -1), 1, 2)
            axis = self.mesh.axis_names[0]
            U, Q = shard_map(kernel, mesh=self.mesh,
                             in_specs=(P(axis), P(axis)),
                             out_specs=(P(axis), P(axis)),
                             check_vma=False)(A, X)
            out = []
            for V, rows, locs in ((U, self.shape[1], self.local_shapes_m),
                                  (Q, self.shape[0], self.local_shapes_n)):
                v = DistributedArray(
                    global_shape=(rows,) + tail, mesh=self.mesh,
                    partition=x.partition, axis=0,
                    local_shapes=tuple(tuple(s) + tail for s in locs),
                    mask=self.mask, dtype=V.dtype)
                v[:] = jnp.swapaxes(V, 1, 2).reshape((rows,) + tail)
                out.append(v)
            return tuple(out)


class MPIStackedBlockDiag(MPIStackedLinearOperator):
    """Diagonal stack of distributed operators acting on a
    StackedDistributedArray (ref ``BlockDiag.py:147-188``)."""

    def __init__(self, ops: Sequence[MPILinearOperator]):
        self.ops = list(ops)
        shape = (int(sum(op.shape[0] for op in ops)),
                 int(sum(op.shape[1] for op in ops)))
        dtype = np.result_type(*[op.dtype for op in ops])
        super().__init__(shape=shape, dtype=dtype)

    def _matvec(self, x: StackedDistributedArray) -> StackedDistributedArray:
        return StackedDistributedArray(
            [op.matvec(d) for op, d in zip(self.ops, x.distarrays)])

    def _rmatvec(self, x: StackedDistributedArray) -> StackedDistributedArray:
        return StackedDistributedArray(
            [op.rmatvec(d) for op, d in zip(self.ops, x.distarrays)])


# the batched block stack travels into jit as a pytree argument
# (multi-process arrays must not be closed over — linearoperator.py)
from ..linearoperator import register_operator_arrays  # noqa: E402
register_operator_arrays(MPIBlockDiag, "_batched")
register_operator_arrays(MPIStackedBlockDiag, "ops")
