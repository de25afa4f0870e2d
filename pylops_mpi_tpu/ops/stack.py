"""Vertical / horizontal stacking of distributed operators.

Rebuild of ``pylops_mpi/basicoperators/VStack.py:21-203`` and
``HStack.py:11-106``. Reference comm pattern: forward takes a BROADCAST
model, every rank computes its own row-block (no comm), output is
SCATTER; adjoint computes per-rank partials ``Lᵢᴴ xᵢ`` then
sum-allreduces into a BROADCAST result (ref ``VStack.py:135-150``).
Here alike blocks run one rank a device under ``shard_map`` and their
partials meet in one ``psum``; other blocks are a static slice-apply
chain whose final sum the XLA partitioner lowers to the same allreduce
over ICI (``MPIVStack``'s docstring).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..diagnostics import trace as _trace
from ..distributedarray import DistributedArray, Partition
from ..stacked import StackedDistributedArray
from ..linearoperator import MPILinearOperator
from ..stackedlinearoperator import MPIStackedLinearOperator
from ._precision import check_compute_dtype, einsum_narrow
from .local import LocalOperator

__all__ = ["MPIVStack", "MPIStackedVStack", "MPIHStack"]


def _nodes(treedef, registry) -> list:
    """``(class, aux, children)`` of every node of a pytree structure,
    depth first: for a registered operator class the aux is the operator
    and ``children`` the names of its pytree children, else ``None``."""
    data = treedef.node_data()
    if data is None:
        return []
    out = [data + (registry.get(data[0]),)]
    for c in treedef.children():
        out += _nodes(c, registry)
    return out


def _alike(a, b) -> bool:
    """Two nodes of :func:`_nodes` are one class with equal state, up
    to their children and the reports the class merges
    (``shard_merge``)."""
    (ca, na, kids), (cb, nb, _) = a, b
    if ca is not cb:
        return False
    if kids is None:
        return _same(na, nb)
    skip = set(kids) | set(getattr(na, "shard_merge", {}))
    va, vb = ({k: v for k, v in vars(n).items() if k not in skip}
              for n in (na, nb))
    return va.keys() == vb.keys() and all(_same(va[k], vb[k]) for k in va)


def _same(a, b) -> bool:
    if isinstance(a, (np.ndarray, jax.Array)) \
            or isinstance(b, (np.ndarray, jax.Array)):
        return np.shape(a) == np.shape(b) \
            and np.result_type(a) == np.result_type(b) \
            and bool(np.array_equal(np.asarray(a), np.asarray(b)))
    try:
        return bool(a == b)
    except Exception:       # noqa: BLE001 - incomparable: not alike
        return a is b


class MPIVStack(MPILinearOperator):
    """Distributed vertical stack (ref ``basicoperators/VStack.py:21-203``).

    Forward: ``y = [L0 x; L1 x; ...]`` with replicated ``x`` — output
    sharded over row-blocks. Adjoint: ``x = Σᵢ Lᵢᴴ yᵢ`` — replicated.

    Homogeneous ``MatrixMult`` blocks (equal shapes, count divisible by
    the mesh) collapse into ONE block-sharded batched GEMM — trace size
    O(1) instead of O(nops), and the MXU sees a single large einsum
    (the ``MPIBlockDiag._try_batch`` treatment; round-2 VERDICT weak
    #4). ``compute_dtype`` (e.g. ``jnp.bfloat16``) narrows the stacked
    block storage, halving HBM traffic of the memory-bound matvec.

    ``overlap`` (``PYLOPS_MPI_TPU_OVERLAP``): the batched adjoint's
    full-row reduction — the partitioner's psum of every device's
    complete partial — becomes an explicit ring reduce-scatter whose
    per-chunk partial GEMM is computed just-in-time at each hop
    (P-1 ``ppermute``\\ s interleaved with P chunk GEMMs, then one
    all-gather to restore the BROADCAST result), so each hop's ICI
    transfer hides behind the next chunk's MXU work. ``off`` keeps the
    einsum-then-psum path bit-identical.

    ``hierarchical`` (``PYLOPS_MPI_TPU_HIERARCHICAL``, round 11): the
    ring form above assumes a single mesh axis, so on a hybrid
    (multi-slice) mesh ``overlap`` instead selects the two-level
    reduction — per-device partial GEMM, then the hierarchical
    reduce-scatter / all-gather pair
    (:func:`~pylops_mpi_tpu.parallel.collectives.hier_psum_scatter` /
    ``hier_all_gather``): the inner ICI stage shrinks the payload
    ``P_ici``-fold before anything touches DCN. With ``hierarchical``
    off a hybrid mesh keeps the bulk einsum-then-psum path.

    **Any other blocks** are applied one by one, in one of two forms
    read from what the stack sees (``form``):

    - ``sharded``, on a mesh of several devices, where the local
      operators are registered (``register_operator_arrays``), alike
      (one tree of classes, equal leaf shapes and dtypes, equal
      non-array state; the reports a class names in ``shard_merge`` are
      merged, not compared) and as many as a multiple of the devices.
      Their array leaves are laid one after another along their leading
      axis, sharded over the mesh (``parallel/mesh.py::concat_sharded``:
      a block's arrays made on the device that owns it ARE that
      device's shard, uncopied), and both applies run under
      ``shard_map``, each device applying its own blocks only. The
      forward takes the replicated model and leaves its rows
      ``SCATTER`` with no collective; the adjoint sums the device's
      partials and ONE ``psum`` over the mesh gives the ``BROADCAST``
      result (named scope ``pmt.collective.stack_reduce``, around it
      alone) — the reference's sum-allreduce, one rank a device;
    - ``replicated`` otherwise (``why``: ``one_device``, ``ragged``,
      ``unregistered``, ``mixed``): every local apply on the global
      operands, cut by the partitioner where it can (``_apply_local``).

    The event ``stack.placement`` (``form`` = ``sharded``,
    ``replicated`` or ``batched``; ``blocks``, ``shards``,
    ``bytes_a_shard`` = the array bytes of the blocks one device
    applies; for ``replicated`` a one-word ``why``) says which, once a
    stack.
    """

    def __init__(self, ops: Sequence[LocalOperator],
                 mask: Optional[Sequence[int]] = None,
                 mesh=None, dtype=None, compute_dtype=None, overlap=None,
                 hierarchical=None):
        from ..utils.deps import overlap_enabled, hierarchical_enabled
        self.ops = list(ops)
        self.mask = tuple(mask) if mask is not None else None
        self.compute_dtype = compute_dtype
        from ..parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()
        cols = {op.shape[1] for op in self.ops}
        if len(cols) != 1:
            raise ValueError("column size mismatch in MPIVStack")
        self.nops = np.asarray([op.shape[0] for op in self.ops])
        from .blockdiag import _chunk_ops
        self.chunks = _chunk_ops(self.ops, int(self.mesh.devices.size))
        self.local_shapes_n = tuple(
            (int(sum(op.shape[0] for op in c)),) for c in self.chunks)
        shape = (int(self.nops.sum()), int(cols.pop()))
        dtype = dtype or np.result_type(*[op.dtype for op in self.ops])
        # autotuner seam (round 10): overlap left at None consults the
        # plan (inert when PYLOPS_MPI_TPU_TUNE=off); an explicit
        # overlap= kwarg or explicit env pin always wins
        from ..utils.deps import overlap_env_pinned
        if overlap is None and not overlap_env_pinned():
            from ..tuning import plan as _tuneplan
            from ..utils.deps import batch_default
            tplan = _tuneplan.get_plan("stack", shape=shape,
                                       dtype=dtype, mesh=self.mesh,
                                       extra={"batch": batch_default()})
            if tplan is not None \
                    and tplan.get("overlap") in ("on", "off"):
                overlap = tplan.get("overlap")
        self._overlap = overlap_enabled(overlap)
        # hybrid-mesh classification (round 11): `_hier_shape` names the
        # (dcn, ici) axes the two-level adjoint reduction stages over;
        # None on flat meshes and under hierarchical=off
        from ..parallel import topology as _topo
        _h = _topo.hybrid_axes(self.mesh)
        self._hier = _h is not None and hierarchical_enabled(hierarchical)
        self._hier_shape = _h if self._hier else None
        super().__init__(shape=shape, dtype=dtype)
        if self.compute_dtype is None:  # env-policy default (f32 only)
            from ._precision import default_compute_dtype
            self.compute_dtype = default_compute_dtype(dtype)
        self._batched, self._batched_adj = self._try_batch()
        self._sharded = self._template = None
        why = None if self._batched is not None else self._shard()
        self.form = "batched" if self._batched is not None else \
            "sharded" if why is None else "replicated"
        # the replicated form's local operators, as a pytree child: a
        # registered local operator's arrays (a Kirchhoff block's
        # tables) then reach the fused solvers as jit arguments; the
        # sharded form's arrays are its stacked leaves, ``_sharded``
        self._local = tuple(self.ops) if self.form == "replicated" \
            else None
        P_ = int(self.mesh.devices.size)
        held = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
            (self._batched, self._sharded, self._local))
            if hasattr(a, "nbytes"))
        _trace.event("stack.placement", cat="schedule", form=self.form,
                     blocks=len(self.ops), shards=P_,
                     bytes_a_shard=held if self.form == "replicated"
                     else held // P_, **({"why": why} if why else {}))

    def _shard(self):
        """Take the sharded form (class docstring) where the blocks
        allow it: set ``_sharded`` (the stacked leaves) and
        ``_template`` (the first block's tree, its ``shard_merge``
        reports merged over the blocks) and return ``None``; else the
        one word why not."""
        from ..linearoperator import OP_ARRAY_PYTREES, operator_is_jit_arg
        from ..parallel.mesh import concat_sharded
        P_ = int(self.mesh.devices.size)
        if P_ == 1:
            return "one_device"
        if len(self.ops) % P_ or len({op.shape for op in self.ops}) != 1:
            return "ragged"
        if not all(operator_is_jit_arg(op) for op in self.ops):
            return "unregistered"
        flat = [jax.tree_util.tree_flatten(op) for op in self.ops]
        nodes = [_nodes(tdef, OP_ARRAY_PYTREES) for _, tdef in flat]
        leaves = [[(np.shape(a), np.result_type(a)) for a in lv]
                  for lv, _ in flat]
        if any(lv != leaves[0] for lv in leaves) or any(
                len(n) != len(nodes[0])
                or not all(map(_alike, n, nodes[0])) for n in nodes):
            return "mixed"
        # the template: fresh copies of the first block's nodes
        # (unflatten copies every node), its reports merged
        tmpl = jax.tree_util.tree_unflatten(flat[0][1], flat[0][0])
        self._template = jax.tree_util.tree_structure(tmpl)
        for i, (_, node, _) in enumerate(_nodes(self._template,
                                                OP_ARRAY_PYTREES)):
            for k, merge in getattr(node, "shard_merge", {}).items():
                setattr(node, k, merge(getattr(n[i][1], k) for n in nodes))
        self._shapes = [s for s, _ in leaves[0]]
        self._sharded = tuple(
            concat_sharded([lv[i] for lv, _ in flat], self.mesh)
            for i in range(len(self._shapes)))
        return None

    def _sharded_apply(self, v: jax.Array, adjoint: bool) -> jax.Array:
        """One apply of the sharded form: ``shard_map`` over the mesh,
        each device its own blocks (the template with the device's
        leaves put in); the adjoint's partials reduced by one ``psum``
        under ``pmt.collective.stack_reduce``."""
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as PSpec
        names = self.mesh.axis_names
        ax = names[0] if len(names) == 1 else tuple(names)
        P_ = int(self.mesh.devices.size)
        k = len(self.ops) // P_

        def blocks(leaves):
            return [jax.tree_util.tree_unflatten(self._template, [
                a.reshape(s) if k == 1 else a.reshape((k,) + s)[j]
                for a, s in zip(leaves, self._shapes)]) for j in range(k)]

        def forward(leaves, x):
            return jnp.concatenate([b.matvec(x) for b in blocks(leaves)])

        def adjoint_(leaves, y):
            rows = y.reshape(k, -1)
            part = sum(b.rmatvec(rows[j])
                       for j, b in enumerate(blocks(leaves)))
            with _trace.span("collective.stack_reduce", cat="collective",
                             shape=part.shape, dtype=part.dtype, axis=ax,
                             n_shards=P_):
                return lax.psum(part, ax)

        spec = PSpec(ax)
        return shard_map(
            adjoint_ if adjoint else forward, mesh=self.mesh,
            in_specs=((spec,) * len(self._sharded),
                      spec if adjoint else PSpec()),
            out_specs=PSpec() if adjoint else spec,
            check_vma=False)(self._sharded, v)

    def _try_batch(self):
        """Homogeneous matrix blocks → one stacked, block-sharded GEMM.
        Accepts plain ``MatrixMult`` rows and ``MatrixMult.H`` rows (the
        ``MPIHStack`` construction) — mixed orientations or shapes fall
        back to the per-op chain. Returns ``(A_stacked, adjoint)`` or
        ``(None, False)``. The adjoint flag lives OUTSIDE the stacked
        array (static python bool) so the operator stays branch-free
        when traced as a pytree argument."""
        from .local import MatrixMult, _Adjoint
        mats, adjs = [], []
        for op in self.ops:
            if isinstance(op, MatrixMult) and not op.otherdims:
                mats.append(op.A_source)
                adjs.append(False)
            elif (isinstance(op, _Adjoint) and isinstance(op.A, MatrixMult)
                    and not op.A.otherdims):
                mats.append(op.A.A_source)
                adjs.append(True)
            else:
                return None, False
        if (len(set(adjs)) != 1 or len({m.shape for m in mats}) != 1
                or len(mats) % int(self.mesh.devices.size) != 0):
            return None, False
        check_compute_dtype(self.compute_dtype,
                            np.result_type(*{m.dtype for m in mats}),
                            "MPIVStack")
        from ..parallel.mesh import stack_sharded
        return stack_sharded(mats, self.mesh, self.compute_dtype), adjs[0]

    def _apply_local(self, op, v, adjoint: bool):
        """One local apply of the generic branch. A local operator that
        is ``whole`` (an interpreted kernel with data-dependent loops,
        see ``LocalOperator.whole``: never on a TPU) gets its operand
        and its result replicated on a mesh of several devices, so the
        partitioner does not carry the stack's row sharding into the
        interpreter's loops."""
        apply = op.rmatvec if adjoint else op.matvec
        if not getattr(op, "whole", False) \
                or int(self.mesh.devices.size) == 1:
            return apply(v)
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.lax.with_sharding_constraint(
            apply(jax.lax.with_sharding_constraint(v, rep)), rep)

    # block (column-batched) inputs add a trailing index to the SAME
    # batched einsums — one widened GEMM, no per-column Python loop
    accepts_block = True

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        # model is replicated (ref requires Partition.BROADCAST input,
        # VStack.py:123-133)
        xg = x.array
        ncol = int(x.global_shape[1]) if x.ndim == 2 else None
        if self._batched is not None:
            A, adj = self._batched, self._batched_adj
            # replicated x against the block-sharded stack: zero
            # communication, output lands SCATTER over blocks
            if adj:
                Y = einsum_narrow("bmn,m->bn" if ncol is None
                                  else "bmn,mk->bnk", A.conj(), xg,
                                  self.compute_dtype, self.dtype)
            else:
                Y = einsum_narrow("bmn,n->bm" if ncol is None
                                  else "bmn,nk->bmk", A, xg,
                                  self.compute_dtype, self.dtype)
            arr = Y.ravel() if ncol is None else Y.reshape(-1, ncol)
        elif ncol is not None:
            # heterogeneous rows: one compiled vmap over columns
            return self._apply_columns(x, forward=True)
        elif self._sharded is not None:
            arr = self._sharded_apply(xg, adjoint=False)
        else:
            arr = jnp.concatenate([self._apply_local(op, xg, False)
                                   for op in self._local or self.ops])
        gshape = self.shape[0] if ncol is None else (self.shape[0], ncol)
        lsh = (self.local_shapes_n if ncol is None
               else tuple(tuple(s) + (ncol,) for s in self.local_shapes_n))
        y = DistributedArray(global_shape=gshape, mesh=self.mesh,
                             partition=Partition.SCATTER, axis=0,
                             local_shapes=lsh,
                             mask=self.mask, dtype=arr.dtype)
        y[:] = arr
        return y

    def _rmatvec_batched_ring(self, x: DistributedArray) -> jax.Array:
        """Ring reduce-scatter form of the batched adjoint reduction
        (overlap on): each device's partial for output chunk ``j`` is a
        restricted GEMM computed at the hop that carries ``j``'s
        accumulator, so the ``ppermute`` of chunk ``s`` flies while
        chunk ``s+1``'s GEMM runs — P-1 permutes interleaved with P
        chunk GEMMs instead of one full GEMM barriered by a psum. A
        final all-gather restores the replicated (BROADCAST) layout."""
        import jax.numpy as _jnp
        from jax import lax
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec

        A, adj = self._batched, self._batched_adj
        P_ = int(self.mesh.devices.size)
        name = self.mesh.axis_names[0]
        nblk = A.shape[0]
        ncol = int(x.global_shape[1]) if x.ndim == 2 else None
        if adj:
            spec, out_len, conj, sl_axis, in_cols = (
                "bmn,bn->m" if ncol is None else "bmn,bnk->mk",
                A.shape[1], False, 1, A.shape[2])
        else:
            spec, out_len, conj, sl_axis, in_cols = (
                "bmn,bm->n" if ncol is None else "bmn,bmk->nk",
                A.shape[2], True, 2, A.shape[1])
        cw = -(-out_len // P_)
        Dp = P_ * cw
        cd, dt = self.compute_dtype, self.dtype

        def kernel(Ab, xb):
            i = lax.axis_index(name)
            if Dp != out_len:
                pad = [(0, 0)] * 3
                pad[sl_axis] = (0, Dp - out_len)
                Ab = _jnp.pad(Ab, pad)
            xl = xb.reshape((nblk // P_, in_cols) if ncol is None
                            else (nblk // P_, in_cols, ncol))

            def chunk(j):
                As = lax.dynamic_slice_in_dim(Ab, j * cw, cw,
                                              axis=sl_axis)
                return einsum_narrow(spec,
                                     _jnp.conj(As) if conj else As,
                                     xl, cd, dt)

            if P_ == 1:
                return chunk(i * 0)
            perm = [(r, (r - 1) % P_) for r in range(P_)]
            buf = chunk((i + 1) % P_)
            for s in range(P_ - 1):
                rb = lax.ppermute(buf, name, perm)
                # the next chunk's GEMM has no dependence on the hop
                buf = rb + chunk((i + s + 2) % P_)
            # device i holds the fully reduced chunk i; replicate
            return lax.all_gather(buf, name, axis=0, tiled=True)

        full = shard_map(kernel, mesh=self.mesh,
                         in_specs=(PSpec(name), PSpec(name)),
                         out_specs=PSpec(None), check_vma=False)(
            A, x.array)
        return full[:out_len]

    def _rmatvec_batched_hier(self, x: DistributedArray) -> jax.Array:
        """Two-level form of the batched adjoint reduction for hybrid
        meshes (overlap on, round 11): each device computes its full
        partial with one GEMM, then the hierarchical reduce-scatter +
        all-gather pair replaces the partitioner's psum — the inner ICI
        ring reduces within each slice first, so the outer DCN stage
        moves ``P_ici``-times-fewer, larger messages."""
        import jax.numpy as _jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as PSpec
        from ..parallel.collectives import (hier_all_gather,
                                            hier_psum_scatter)

        A, adj = self._batched, self._batched_adj
        dcn_ax, ici_ax, D, I = self._hier_shape
        P_ = D * I
        nblk = A.shape[0]
        ncol = int(x.global_shape[1]) if x.ndim == 2 else None
        if adj:
            spec, out_len, conj, in_cols = (
                "bmn,bn->m" if ncol is None else "bmn,bnk->mk",
                A.shape[1], False, A.shape[2])
        else:
            spec, out_len, conj, in_cols = (
                "bmn,bm->n" if ncol is None else "bmn,bmk->nk",
                A.shape[2], True, A.shape[1])
        Dp = P_ * (-(-out_len // P_))
        cd, dt = self.compute_dtype, self.dtype
        names = tuple(self.mesh.axis_names)

        def kernel(Ab, xb):
            xl = xb.reshape((nblk // P_, in_cols) if ncol is None
                            else (nblk // P_, in_cols, ncol))
            part = einsum_narrow(spec, _jnp.conj(Ab) if conj else Ab,
                                 xl, cd, dt)
            if Dp != out_len:
                pad = [(0, 0)] * part.ndim
                pad[0] = (0, Dp - out_len)
                part = _jnp.pad(part, pad)
            red = hier_psum_scatter(part, dcn_ax, ici_ax, D, I, dim=0)
            return hier_all_gather(red, dcn_ax, ici_ax, D, I, dim=0)

        full = shard_map(kernel, mesh=self.mesh,
                         in_specs=(PSpec(names), PSpec(names)),
                         out_specs=PSpec(None), check_vma=False)(
            A, x.array)
        return full[:out_len]

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        ncol = int(x.global_shape[1]) if x.ndim == 2 else None
        if self._batched is not None:
            A, adj = self._batched, self._batched_adj
            nblk = A.shape[0]
            # the flat ring is written against a single mesh axis
            # (axis_names[0] / devices.size); hybrid meshes take the
            # two-level path when hierarchical is enabled and the bulk
            # einsum-then-psum otherwise
            if self._overlap and len(self.mesh.axis_names) == 1 \
                    and int(self.mesh.devices.size) > 1:
                acc = self._rmatvec_batched_ring(x)
            elif self._overlap and self._hier:
                acc = self._rmatvec_batched_hier(x)
            # per-block partials reduced over the sharded block axis —
            # the partitioner lowers the contraction to one psum, the
            # reference's sum-allreduce (ref VStack.py:135-150)
            elif adj:
                xr = x.array.reshape((nblk, A.shape[2]) if ncol is None
                                     else (nblk, A.shape[2], ncol))
                acc = einsum_narrow("bmn,bn->m" if ncol is None
                                    else "bmn,bnk->mk", A, xr,
                                    self.compute_dtype, self.dtype)
            else:
                xr = x.array.reshape((nblk, A.shape[1]) if ncol is None
                                     else (nblk, A.shape[1], ncol))
                acc = einsum_narrow("bmn,bm->n" if ncol is None
                                    else "bmn,bmk->nk", A.conj(), xr,
                                    self.compute_dtype, self.dtype)
        elif ncol is not None:
            return self._apply_columns(x, forward=False)
        elif self._sharded is not None:
            acc = self._sharded_apply(x.array, adjoint=True)
        else:
            offs = np.concatenate([[0], np.cumsum(self.nops)])
            acc = None
            for op, lo, hi in zip(self._local or self.ops, offs[:-1],
                                  offs[1:]):
                part = self._apply_local(op, x.array[int(lo):int(hi)],
                                         True)
                acc = part if acc is None else acc + part
        gshape = self.shape[1] if ncol is None else (self.shape[1], ncol)
        y = DistributedArray(global_shape=gshape, mesh=self.mesh,
                             partition=Partition.BROADCAST,
                             mask=self.mask, dtype=acc.dtype)
        y[:] = acc
        return y


class MPIStackedVStack(MPIStackedLinearOperator):
    """Vertical stack of distributed operators: one shared model, stacked
    data (ref ``VStack.py:153-203``). Output is a StackedDistributedArray
    with one component per operator.

    ``dims`` / ``dimsd`` are metadata read from the children, no apply
    uses them: ``dims`` is the children's where they all declare the
    same (else the flat ``(shape[1],)``), ``dimsd`` one tuple a child,
    as the stacked data has one component a child. The fused solvers
    hold their carries so (``solvers/basic.py::_carry_shape``)."""

    def __init__(self, ops: Sequence[MPILinearOperator]):
        self.ops = list(ops)
        if len({op.shape[1] for op in self.ops}) != 1:
            raise ValueError("column size mismatch in MPIStackedVStack")
        dims = {tuple(op.dims) for op in self.ops}
        self.dims = dims.pop() if len(dims) == 1 else None
        self.dimsd = tuple(tuple(op.dimsd) for op in self.ops)
        shape = (int(sum(op.shape[0] for op in self.ops)), self.ops[0].shape[1])
        dtype = np.result_type(*[op.dtype for op in self.ops])
        super().__init__(shape=shape, dtype=dtype)

    def _matvec(self, x: DistributedArray) -> StackedDistributedArray:
        return StackedDistributedArray([op.matvec(x) for op in self.ops])

    def _rmatvec(self, x: StackedDistributedArray) -> DistributedArray:
        y = self.ops[0].rmatvec(x.distarrays[0])
        for op, d in zip(self.ops[1:], x.distarrays[1:]):
            y = y + op.rmatvec(d)
        return y


class MPIHStack(MPILinearOperator):
    """Horizontal stack, implemented as the adjoint of a VStack of
    adjoints — exactly the reference's trick (ref ``HStack.py:98-100``)."""

    accepts_block = True  # delegates to the block-capable VStack paths

    def __init__(self, ops: Sequence[LocalOperator],
                 mask: Optional[Sequence[int]] = None,
                 mesh=None, dtype=None, compute_dtype=None, overlap=None,
                 hierarchical=None):
        self.vstack = MPIVStack([op.H for op in ops], mask=mask, mesh=mesh,
                                dtype=dtype, compute_dtype=compute_dtype,
                                overlap=overlap, hierarchical=hierarchical)
        self.ops = self.vstack.ops
        shape = (self.vstack.shape[1], self.vstack.shape[0])
        super().__init__(shape=shape, dtype=self.vstack.dtype)

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        return self.vstack._rmatvec(x)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        return self.vstack._matvec(x)


# batched stacks travel into jit as pytree arguments (multi-process
# arrays must not be closed over — see linearoperator.py registry); so
# do a sharded stack's stacked leaves, and the local operators of a
# replicated stack where their classes are registered (an unregistered
# one is an opaque leaf: closure capture, as before)
from ..linearoperator import register_operator_arrays  # noqa: E402
register_operator_arrays(MPIVStack, "_batched", "_local", "_sharded")
register_operator_arrays(MPIHStack, "vstack")
register_operator_arrays(MPIStackedVStack, "ops")
