"""Distributed linear-operator abstraction with lazy composition algebra.

Rebuild of ``pylops_mpi/LinearOperator.py`` (ref lines 16-602). Operators
map :class:`DistributedArray` → :class:`DistributedArray`; every
``_matvec``/``_rmatvec`` is pure and jit-traceable, so whole solver loops
(including all operator algebra below) compile to a single XLA program —
the reference instead interprets the expression tree per call in Python
with host-synced collectives in between.

Lazy wrappers mirror ref ``LinearOperator.py:408-580``:
``_AdjointLinearOperator`` (swap mat/rmat), ``_TransposedLinearOperator``
(conj∘rmat∘conj), ``_ProductLinearOperator``, ``_ScaledLinearOperator``,
``_SumLinearOperator``, ``_PowerLinearOperator``, ``_ConjLinearOperator``.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import numpy as np
import jax.numpy as jnp

from .distributedarray import DistributedArray, Partition
from .stacked import StackedDistributedArray

__all__ = ["MPILinearOperator", "LinearOperator", "aslinearoperator",
           "asmpilinearoperator"]

VectorLike = Union[DistributedArray, StackedDistributedArray]


def _scalar_like(x) -> bool:
    """Python/numpy scalars plus 0-d arrays (jax or numpy) — the
    latter possibly TRACED, which is how a learnable scalar weight
    (``eps * Reg`` under ``jax.grad``) enters the operator algebra."""
    if np.isscalar(x):
        return True
    import jax
    return (isinstance(x, (jax.Array, np.ndarray, np.generic))
            and np.ndim(x) == 0)


class MPILinearOperator:
    """Abstract distributed linear operator
    (ref ``pylops_mpi/LinearOperator.py:16-168``).

    Subclasses implement ``_matvec``/``_rmatvec`` on
    :class:`DistributedArray`. ``Op`` wraps a *local* operator (our
    jnp-based :mod:`ops.local` analog of a pylops op) applied to the
    array's global value — the one-controller equivalent of the
    reference's per-rank apply (ref ``LinearOperator.py:194-242``),
    which in practice targets replicated arrays.
    """

    def __init__(self, Op=None, shape: Optional[Tuple[int, int]] = None,
                 dtype=None):
        self.Op = Op
        if Op is not None:
            self.shape = Op.shape if shape is None else shape
            self.dtype = Op.dtype if dtype is None else dtype
            if shape is None:
                # the wrapped local operator's N-D shapes, as every
                # lazy wrapper forwards its operand's (metadata: the
                # applies work on the flat vector)
                self.dims = getattr(Op, "dims", None)
                self.dimsd = getattr(Op, "dimsd", None)
        else:
            self.shape = shape
            self.dtype = np.dtype(dtype) if dtype is not None else None
        if not hasattr(self, "dims") or self.dims is None:
            self.dims = (self.shape[1],) if self.shape else None
        if not hasattr(self, "dimsd") or self.dimsd is None:
            self.dimsd = (self.shape[0],) if self.shape else None

    # subclasses may pre-set dims/dimsd before calling super().__init__
    dims: Optional[Tuple[int, ...]] = None
    dimsd: Optional[Tuple[int, ...]] = None

    # Block (column-batched) applies: a ``(N, K)`` DistributedArray is K
    # independent model vectors sharing one operator apply. Operators
    # whose ``_matvec``/``_rmatvec`` natively widen their contraction
    # over the trailing column axis set ``accepts_block = True``;
    # everything else falls back to a single compiled ``jax.vmap`` over
    # columns (no per-column Python loop either way).
    accepts_block = False

    # ------------------------------------------------------------- apply
    def matvec(self, x: VectorLike) -> VectorLike:
        """Forward apply with global-shape check
        (ref ``LinearOperator.py:170-192``). Accepts ``(N,)`` or the
        block form ``(N, K)`` — K model columns through one apply.
        Opens a diagnostics span (``PYLOPS_MPI_TPU_TRACE``) tagged with
        the operator class, shape, dtype and mesh axes; compositions
        nest naturally."""
        M, N = self.shape
        block = (isinstance(x, DistributedArray) and x.ndim == 2
                 and x.global_shape[0] == N)
        if isinstance(x, DistributedArray) and not block \
                and x.global_shape != (N,):
            raise ValueError(
                f"dimension mismatch: operator {self.shape}, x {x.global_shape}")
        from .diagnostics import trace
        with trace.op_span(self, "matvec"):
            if block and not self.accepts_block:
                return self._apply_columns(x, forward=True)
            return self._matvec(x)

    def rmatvec(self, x: VectorLike) -> VectorLike:
        """Adjoint apply with global-shape check
        (ref ``LinearOperator.py:206-230``). Accepts ``(M,)`` or the
        block form ``(M, K)``; traced like :meth:`matvec`."""
        M, N = self.shape
        block = (isinstance(x, DistributedArray) and x.ndim == 2
                 and x.global_shape[0] == M)
        if isinstance(x, DistributedArray) and not block \
                and x.global_shape != (M,):
            raise ValueError(
                f"dimension mismatch: operator {self.shape}, x {x.global_shape}")
        from .diagnostics import trace
        with trace.op_span(self, "rmatvec"):
            if block and not self.accepts_block:
                return self._apply_columns(x, forward=False)
            return self._rmatvec(x)

    def _apply_columns(self, x: "DistributedArray", forward: bool):
        """Generic block fallback: ``jax.vmap`` the single-column apply
        over the trailing axis — one traced program for all K columns.
        Operators with a native widened contraction (``accepts_block``)
        never reach this."""
        import jax
        fn = self._matvec if forward else self._rmatvec
        row_locals = tuple((s[0],) for s in x.local_shapes)
        tmpl = {}

        def one(col):
            xi = DistributedArray._wrap(
                col, x, global_shape=(x.global_shape[0],),
                local_shapes=row_locals)
            yi = fn(xi)
            if not isinstance(yi, DistributedArray):
                raise TypeError(
                    f"{type(self).__name__}: block apply supports "
                    f"DistributedArray results only, got "
                    f"{type(yi).__name__}")
            tmpl["like"] = yi
            return yi._arr

        out = jax.vmap(one, in_axes=1, out_axes=1)(x._arr)
        like = tmpl["like"]
        K = x.global_shape[1]
        return DistributedArray._wrap(
            out, like, global_shape=like.global_shape + (K,),
            local_shapes=tuple(tuple(s) + (K,) for s in like.local_shapes))

    def _wrap_local(self, y, x: "DistributedArray", n: int):
        out = DistributedArray(global_shape=n, mesh=x.mesh,
                               partition=x.partition, axis=0,
                               mask=x.mask, dtype=y.dtype)
        out[:] = y
        return out

    def _matvec(self, x: VectorLike) -> VectorLike:
        if self.Op is not None:
            return self._wrap_local(self.Op.matvec(x.array.ravel()), x,
                                    self.shape[0])
        raise NotImplementedError

    def _rmatvec(self, x: VectorLike) -> VectorLike:
        if self.Op is not None:
            return self._wrap_local(self.Op.rmatvec(x.array.ravel()), x,
                                    self.shape[1])
        raise NotImplementedError

    # ------------------------------------------------- normal-equations
    # ``(u, q) = (Opᴴ Op x, Op x)`` — the CGLS hot pair. The generic
    # path is two sweeps; operators that can produce both in one memory
    # pass (e.g. MPIBlockDiag's Pallas kernel) override this and set
    # ``has_fused_normal``.
    has_fused_normal = False

    def normal_matvec(self, x: VectorLike):
        q = self.matvec(x)
        return self.rmatvec(q), q

    def prefers_fused_normal(self, x: VectorLike) -> bool:
        """What ``cgls(normal=None)`` asks: would ``normal_matvec(x)``
        run a compiled one-sweep kernel that beats ``matvec`` +
        ``rmatvec`` for THIS vector? Only then is the one-sweep CGLS
        recurrence worth its extra carry. The generic pair never is."""
        return False

    # ``(q, adjoint) = fresh_normal_matvec(c, s)``: ``q = Op c`` and
    # ``adjoint(t) = Opᴴ (s − t q)`` from one memory pass — what the
    # fresh-residual CGLS body needs (``solvers/basic.py``). Operators
    # that can make it define the method and set ``has_fresh_normal``
    # (``ops/mdc.py``'s chain); no other operator offers it.
    has_fresh_normal = False

    # ----------------------------------------------------------- algebra
    def dot(self, x):
        """Operator-operator, operator-scalar or operator-vector product
        (ref ``LinearOperator.py:244-280``). Scalars include 0-d
        jax/numpy arrays — possibly TRACED (a learnable ``eps * Reg``
        weight under ``jax.grad``): the scale rides in ``args`` as a
        differentiable pytree leaf."""
        if isinstance(x, MPILinearOperator):
            return _ProductLinearOperator(self, x)
        if _scalar_like(x):
            return _ScaledLinearOperator(self, x)
        if isinstance(x, StackedDistributedArray) or x.ndim == 1:
            return self.matvec(x)
        if x.ndim == 2 and x.global_shape[0] == self.shape[1]:
            return self.matvec(x)  # block (column-batched) apply
        raise ValueError(f"expected 1-d DistributedArray, got {x.global_shape!r}")

    def adjoint(self):
        return self._adjoint()

    H = property(adjoint)

    def transpose(self):
        return self._transpose()

    T = property(transpose)

    def conj(self):
        return _ConjLinearOperator(self)

    def _adjoint(self):
        return _AdjointLinearOperator(self)

    def _transpose(self):
        return _TransposedLinearOperator(self)

    def __mul__(self, x):
        return self.dot(x)

    def __rmul__(self, x):
        if _scalar_like(x):
            return _ScaledLinearOperator(self, x)
        return NotImplemented

    def __matmul__(self, x):
        if _scalar_like(x):
            raise ValueError("Scalar not allowed, use * instead")
        return self.__mul__(x)

    def __rmatmul__(self, x):
        if _scalar_like(x):
            raise ValueError("Scalar not allowed, use * instead")
        return self.__rmul__(x)

    def __pow__(self, p):
        return _PowerLinearOperator(self, p)

    def __add__(self, x):
        return _SumLinearOperator(self, x)

    def __neg__(self):
        return _ScaledLinearOperator(self, -1)

    def __sub__(self, x):
        return self.__add__(-x)

    def checkpointed(self) -> "MPILinearOperator":
        """Wrap matvec/rmatvec in :func:`jax.checkpoint` (remat): under
        reverse-mode AD the operator's intermediates are recomputed in
        the backward pass instead of stored — the standard
        FLOPs-for-HBM trade for long composed chains whose activation
        memory would not fit. No effect outside AD."""
        return _CheckpointedLinearOperator(self)

    def todifferentiable(self, mode: str = "vjp", params=None) \
            -> "MPILinearOperator":
        """Wrap the operator with the adjoint autodiff rules: under
        ``jax.grad``/``jax.vjp`` (``mode="vjp"``) or ``jax.jvp``
        (``mode="jvp"``) its applies differentiate by the hand-written
        ``rmatvec``/``matvec`` instead of a machine-derived transpose
        of the forward collective schedule. See
        :class:`pylops_mpi_tpu.autodiff.DifferentiableOperator` for the
        ``params`` (operator-leaf cotangents) contract."""
        from .autodiff.rules import make_differentiable
        return make_differentiable(self, mode=mode, params=params)

    def todense(self) -> np.ndarray:
        """Dense matrix of the operator, by applying it to each identity
        column and gathering (serial-pylops convenience; the MPI
        reference has no equivalent because no rank holds the global
        matrix). O(n) matvecs — intended for tests and small operators
        (warned above n=8192)."""
        from .distributedarray import DistributedArray
        m, n = self.shape
        if n > 8192:
            import warnings
            warnings.warn(
                f"todense() runs {n} distributed matvecs and builds an "
                f"{m}x{n} dense matrix on host — tests/small operators "
                "only", stacklevel=2)
        dt = np.dtype(self.dtype)
        mesh = getattr(self, "mesh", None)
        shapes = getattr(self, "local_shapes_m",
                         getattr(self, "local_dim_sizes", None))
        out = np.zeros((m, n), dtype=dt)
        for j in range(n):
            e = np.zeros(n, dtype=dt)
            e[j] = 1
            col = self.matvec(DistributedArray.to_dist(
                e, mesh=mesh, local_shapes=shapes))
            out[:, j] = np.asarray(col.asarray())
        return out

    def __repr__(self):
        M, N = self.shape
        dt = "unspecified dtype" if self.dtype is None else f"dtype={self.dtype}"
        return f"<{M}x{N} {self.__class__.__name__} with {dt}>"


# Friendly alias — the TPU build has no MPI, but the reference-facing name
# is kept so user scripts port by changing only the import.
LinearOperator = MPILinearOperator


class _AdjointLinearOperator(MPILinearOperator):
    """ref ``LinearOperator.py:408-421``"""

    # all lazy wrappers delegate through the sub-operators' PUBLIC
    # matvec/rmatvec (which route block inputs to the child's native
    # widened contraction or its vmap fallback), so the wrappers
    # themselves accept the column axis
    accepts_block = True

    def __init__(self, A: MPILinearOperator):
        self.dims, self.dimsd = A.dimsd, A.dims
        super().__init__(shape=(A.shape[1], A.shape[0]), dtype=A.dtype)
        self.args = (A,)

    @property
    def A(self):
        # via args so pytree unflattening (which swaps args) keeps the
        # methods reading the traced sub-operator, not a stale copy
        return self.args[0]

    def _matvec(self, x):
        return self.A.rmatvec(x)

    def _rmatvec(self, x):
        return self.A.matvec(x)


class _TransposedLinearOperator(MPILinearOperator):
    """transpose = conj ∘ rmatvec ∘ conj (ref ``LinearOperator.py:424-443``)"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator):
        self.dims, self.dimsd = A.dimsd, A.dims
        super().__init__(shape=(A.shape[1], A.shape[0]), dtype=A.dtype)
        self.args = (A,)

    @property
    def A(self):
        return self.args[0]  # see _AdjointLinearOperator.A

    def _matvec(self, x):
        return self.A.rmatvec(x.conj()).conj()

    def _rmatvec(self, x):
        return self.A.matvec(x.conj()).conj()


class _ProductLinearOperator(MPILinearOperator):
    """ref ``LinearOperator.py:446-466``"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator, B: MPILinearOperator):
        if A.shape[1] != B.shape[0]:
            raise ValueError(f"cannot multiply {A} and {B}: shape mismatch")
        self.args = (A, B)
        self.dims, self.dimsd = B.dims, A.dimsd
        super().__init__(shape=(A.shape[0], B.shape[1]),
                         dtype=_get_dtype([A, B]))

    def _matvec(self, x):
        return self.args[0].matvec(self.args[1].matvec(x))

    def _rmatvec(self, x):
        return self.args[1].rmatvec(self.args[0].rmatvec(x))

    def _adjoint(self):
        A, B = self.args
        return B.H * A.H


class _ScaledLinearOperator(MPILinearOperator):
    """ref ``LinearOperator.py:469-496``"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator, alpha):
        if not _scalar_like(alpha):
            raise ValueError("scalar expected as alpha")
        self.args = (A, alpha)
        self.dims, self.dimsd = A.dims, A.dimsd
        # 0-d arrays (possibly traced) carry their own dtype; python
        # scalars keep the type-promotion rule of the reference
        adt = getattr(alpha, "dtype", None)
        super().__init__(shape=A.shape,
                         dtype=_get_dtype([A], [adt if adt is not None
                                                else type(alpha)]))

    @staticmethod
    def _conj(alpha):
        # host conj for concrete scalars (keeps scalar dispatch in
        # ``dot`` working); jnp.conj for the traced leaf the pytree
        # registration turns alpha into under jit
        return np.conj(alpha) if np.isscalar(alpha) else jnp.conj(alpha)

    def _matvec(self, x):
        return self.args[0].matvec(x) * self.args[1]

    def _rmatvec(self, x):
        return self.args[0].rmatvec(x) * self._conj(self.args[1])

    def _adjoint(self):
        A, alpha = self.args
        return A.H * self._conj(alpha)


class _SumLinearOperator(MPILinearOperator):
    """ref ``LinearOperator.py:499-524``"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator, B: MPILinearOperator):
        if A.shape != B.shape:
            raise ValueError(f"cannot add {A} and {B}: shape mismatch")
        self.args = (A, B)
        self.dims, self.dimsd = A.dims, A.dimsd
        super().__init__(shape=A.shape, dtype=_get_dtype([A, B]))

    def _matvec(self, x):
        return self.args[0].matvec(x) + self.args[1].matvec(x)

    def _rmatvec(self, x):
        return self.args[0].rmatvec(x) + self.args[1].rmatvec(x)

    def _adjoint(self):
        A, B = self.args
        return A.H + B.H


class _PowerLinearOperator(MPILinearOperator):
    """repeat-apply (ref ``LinearOperator.py:527-552``)"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator, p: int):
        if A.shape[0] != A.shape[1]:
            raise ValueError("square operator expected")
        if not isinstance(p, (int, np.integer)) or p < 0:
            raise ValueError("non-negative integer expected as p")
        self.args = (A, p)
        # p also kept OUTSIDE args: when the operator travels into jit
        # as a pytree argument, args' leaves are traced — the loop
        # bound must stay a static python int
        self._p = int(p)
        self.dims, self.dimsd = A.dims, A.dimsd
        super().__init__(shape=A.shape, dtype=A.dtype)

    def _power(self, fun, x):
        res = x.copy()
        for _ in range(self._p):
            res = fun(res)
        return res

    def _matvec(self, x):
        return self._power(self.args[0].matvec, x)

    def _rmatvec(self, x):
        return self._power(self.args[0].rmatvec, x)


class _ConjLinearOperator(MPILinearOperator):
    """ref ``LinearOperator.py:555-580``"""

    accepts_block = True

    def __init__(self, A: MPILinearOperator):
        self.dims, self.dimsd = A.dims, A.dimsd
        super().__init__(shape=A.shape, dtype=A.dtype)
        self.args = (A,)

    @property
    def A(self):
        return self.args[0]  # see _AdjointLinearOperator.A

    def _matvec(self, x):
        return self.A.matvec(x.conj()).conj()

    def _rmatvec(self, x):
        return self.A.rmatvec(x.conj()).conj()

    def _adjoint(self):
        return _ConjLinearOperator(self.A.H)


class _CheckpointedLinearOperator(MPILinearOperator):
    """Remat wrapper: matvec/rmatvec run under :func:`jax.checkpoint` so
    reverse-mode AD recomputes their intermediates instead of storing
    them (TPU HBM lever for long composed chains)."""

    accepts_block = True

    # layout metadata forwarded so dottest/todense/solvers see the same
    # shard layout on the wrapper as on the wrapped operator
    _FORWARDED = ("dims", "dimsd", "mesh", "local_shapes_m",
                  "local_shapes_n", "local_dim_sizes",
                  "local_extent_sizes")

    def __init__(self, A: MPILinearOperator):
        for attr in self._FORWARDED:
            if hasattr(A, attr):
                setattr(self, attr, getattr(A, attr))
        super().__init__(shape=A.shape, dtype=A.dtype)
        self.args = (A,)

    @property
    def A(self):
        return self.args[0]  # see _AdjointLinearOperator.A

    # checkpoint wrapping happens per call (cheap at trace time): a
    # bound-at-init closure would pin the ORIGINAL operator's buffers
    # even after pytree unflattening swapped in traced ones
    def _matvec(self, x):
        import jax
        return jax.checkpoint(self.args[0].matvec)(x)

    def _rmatvec(self, x):
        import jax
        return jax.checkpoint(self.args[0].rmatvec)(x)

    def _adjoint(self):
        return _CheckpointedLinearOperator(self.A.H)


def _get_dtype(operators, dtypes=None):
    if dtypes is None:
        dtypes = []
    for op in operators:
        if op is not None and hasattr(op, "dtype") and op.dtype is not None:
            dtypes.append(op.dtype)
    return np.result_type(*dtypes) if dtypes else None


def aslinearoperator(Op) -> MPILinearOperator:
    """Wrap a local (jnp-level) operator as a distributed one
    (ref ``asmpilinearoperator``, ``LinearOperator.py:583-602``)."""
    if isinstance(Op, MPILinearOperator):
        return Op
    return MPILinearOperator(Op=Op)


asmpilinearoperator = aslinearoperator


# --------------------------------------------------- operators as pytrees
# Multi-process JAX forbids closing over arrays that span non-addressable
# devices: "Please pass such arrays as arguments to the function". The
# fused solvers therefore pass the OPERATOR itself as a jit argument
# whenever its class is registered here — its device buffers flatten to
# pytree children while everything else (shapes, meshes, sub-operator
# lists) rides along as aux, compared by object identity for the
# compilation cache. This is what makes ``cgls(...)`` work unchanged on
# a 2-process ``jax.distributed`` CPU job (tests/multihost_worker.py)
# and on multi-host pods, replacing the reference's per-rank operator
# state (each rank owning only its local block).

# registered class -> the names of its children (``register_operator_arrays``)
OP_ARRAY_PYTREES = {}


def register_operator_arrays(cls, *attrs: str) -> None:
    """Register ``cls`` as a jax pytree whose children are the device
    buffers (or registered sub-operators) stored in ``attrs``; the
    instance itself is the aux. Unflatten shallow-copies the instance
    and swaps in the (possibly traced) children, so operator methods
    run unmodified under trace."""
    import copy
    import jax

    def _flatten(op):
        return tuple(getattr(op, a) for a in attrs), op

    def _unflatten(aux, children):
        new = copy.copy(aux)
        for a, c in zip(attrs, children):
            setattr(new, a, c)
        return new

    jax.tree_util.register_pytree_node(cls, _flatten, _unflatten)
    OP_ARRAY_PYTREES[cls] = attrs


def operator_is_jit_arg(Op) -> bool:
    """True when ``Op`` can safely travel into ``jax.jit`` as a pytree
    argument: its class is registered AND every flattened leaf is an
    array/scalar. A registered wrapper composed over an UNREGISTERED
    user operator flattens that child to an opaque leaf, which jit
    would reject — such compositions fall back to closure capture
    (works single-process; multi-process users must register their
    classes, see docs/multihost.md)."""
    if type(Op) not in OP_ARRAY_PYTREES:
        return False
    import jax
    import numpy as _np
    return all(
        l is None or isinstance(l, (jax.Array, _np.ndarray, _np.number,
                                    int, float, complex, bool))
        for l in jax.tree_util.tree_leaves(Op))


# The base class (aslinearoperator instances) and every lazy wrapper:
# wrappers expose their sub-operators through ``args`` so compositions
# like (Op.H @ Op) or eps*Reg recurse into the registered leaves.
# Array-less classes register with NO attrs — they still need to be
# pytree nodes to be valid CHILDREN of a registered wrapper. The
# _Power wrapper's exponent and _Scaled's alpha ride in args as traced
# leaves; the static copies (_p / dtype math) stay in aux.
register_operator_arrays(MPILinearOperator)
for _w in (_AdjointLinearOperator, _TransposedLinearOperator,
           _ProductLinearOperator, _ScaledLinearOperator,
           _SumLinearOperator, _PowerLinearOperator,
           _ConjLinearOperator, _CheckpointedLinearOperator):
    register_operator_arrays(_w, "args")
