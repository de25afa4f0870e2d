"""Local (single logical block) linear operators on ``jnp`` arrays.

The reference delegates all rank-local compute to serial pylops
operators (e.g. ``MPIBlockDiag([pylops.MatrixMult(...)])``,
ref ``pylops_mpi/basicoperators/BlockDiag.py:122-132``). The TPU build
has no pylops dependency: this module provides the jnp-native local
operator algebra those distributed operators compose over. Every
``matvec``/``rmatvec`` is a pure jittable function of flat 1-D arrays,
so composed distributed operators trace into a single XLA program.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import dft
from ..diagnostics import trace as _trace

__all__ = [
    "LocalOperator", "MatrixMult", "Identity", "Diagonal", "Zero",
    "Transpose", "FirstDerivative", "SecondDerivative", "Laplacian",
    "Roll", "Pad", "Flip", "FunctionOperator", "VStack", "HStack",
    "BlockDiag", "FFT", "Conv1D", "NonStationaryConvolve1D",
]


def _scoped(apply):
    """Run a local apply under the span ``local.<Class>`` (the rule of
    ``diagnostics/trace.py``: a named scope ``pmt.local.<Class>`` under
    a jit trace), so a device trace splits the distributed operator
    that composes local ones — ``pmt.MPIBlockDiag.matvec`` into
    convolution and derivative."""
    @functools.wraps(apply)
    def wrapped(self, x):
        with _trace.span("local." + type(self).__name__):
            return apply(self, x)
    return wrapped


class LocalOperator:
    """Minimal pylops-like operator protocol over jnp arrays."""

    # True where the apply must not be cut by the partitioner: an
    # INTERPRETED Pallas kernel whose loops' trip counts are data (cut
    # over a mesh, each shard's loop would hold collectives the others
    # never reach). A distributed operator composed over such an
    # operator keeps the operand and the result replicated around the
    # apply (``MPIVStack._apply_local``); a product is whole where a
    # factor is. A compiled kernel is opaque to the partitioner as it
    # is, so on a TPU nothing answers True.
    whole = False
    # attributes that report on the operator rather than define its
    # apply, each with how a sharded ``MPIVStack`` merges it over its
    # blocks (``ops/stack.py``): blocks that differ only there are alike
    shard_merge: dict = {}

    def __init__(self, dims, dimsd, dtype=None, name: str = "L"):
        self.dims = tuple(int(d) for d in np.ravel(dims))
        self.dimsd = tuple(int(d) for d in np.ravel(dimsd))
        self.shape = (int(np.prod(self.dimsd)), int(np.prod(self.dims)))
        self.dtype = np.dtype(dtype) if dtype is not None else np.dtype("float32")
        self.name = name

    def _matvec(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def _rmatvec(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def matvec(self, x: jax.Array) -> jax.Array:
        return self._matvec(jnp.asarray(x).ravel()).ravel()

    def rmatvec(self, x: jax.Array) -> jax.Array:
        return self._rmatvec(jnp.asarray(x).ravel()).ravel()

    # ------------------------------------------------------------ algebra
    @property
    def H(self) -> "LocalOperator":
        return _Adjoint(self)

    @property
    def T(self) -> "LocalOperator":
        return _Transposed(self)

    def conj(self) -> "LocalOperator":
        return _Conj(self)

    def __mul__(self, x):
        if np.isscalar(x):
            return _Scaled(self, x)
        if isinstance(x, LocalOperator):
            return _Product(self, x)
        return self.matvec(x)

    def __rmul__(self, x):
        if np.isscalar(x):
            return _Scaled(self, x)
        return NotImplemented

    def __matmul__(self, x):
        if isinstance(x, LocalOperator):
            return _Product(self, x)
        return self.matvec(x)

    def __add__(self, x):
        return _Sum(self, x)

    def __neg__(self):
        return _Scaled(self, -1)

    def __sub__(self, x):
        return _Sum(self, _Scaled(x, -1))

    def todense(self) -> np.ndarray:
        eye = jnp.eye(self.shape[1], dtype=self.dtype)
        cols = jax.vmap(self.matvec, in_axes=1, out_axes=1)(eye)
        return np.asarray(cols)

    def __repr__(self):
        return f"<{self.shape[0]}x{self.shape[1]} {type(self).__name__} dtype={self.dtype}>"


class _Adjoint(LocalOperator):
    def __init__(self, A):
        super().__init__(A.dimsd, A.dims, dtype=A.dtype)
        self.A = A

    def _matvec(self, x):
        return self.A._rmatvec(x)

    def _rmatvec(self, x):
        return self.A._matvec(x)

    @property
    def H(self):
        return self.A


class _Transposed(LocalOperator):
    def __init__(self, A):
        super().__init__(A.dimsd, A.dims, dtype=A.dtype)
        self.A = A

    def _matvec(self, x):
        return jnp.conj(self.A._rmatvec(jnp.conj(x)))

    def _rmatvec(self, x):
        return jnp.conj(self.A._matvec(jnp.conj(x)))


class _Conj(LocalOperator):
    def __init__(self, A):
        super().__init__(A.dims, A.dimsd, dtype=A.dtype)
        self.A = A

    def _matvec(self, x):
        return jnp.conj(self.A._matvec(jnp.conj(x)))

    def _rmatvec(self, x):
        return jnp.conj(self.A._rmatvec(jnp.conj(x)))


class _Scaled(LocalOperator):
    def __init__(self, A, alpha):
        super().__init__(A.dims, A.dimsd,
                         dtype=np.result_type(A.dtype, type(alpha)))
        self.A, self.alpha = A, alpha

    def _matvec(self, x):
        return self.alpha * self.A._matvec(x)

    def _rmatvec(self, x):
        # the scalar's own conjugate keeps its type: np.conj of a Python
        # float is a float64, which would widen an f32 adjoint under x64
        return self.alpha.conjugate() * self.A._rmatvec(x)


class _Product(LocalOperator):
    def __init__(self, A, B):
        if A.shape[1] != B.shape[0]:
            raise ValueError(f"shape mismatch {A.shape} @ {B.shape}")
        super().__init__(B.dims, A.dimsd, dtype=np.result_type(A.dtype, B.dtype))
        self.A, self.B = A, B

    @property
    def whole(self):
        return self.A.whole or self.B.whole

    def _matvec(self, x):
        return self.A.matvec(self.B.matvec(x))

    def _rmatvec(self, x):
        return self.B.rmatvec(self.A.rmatvec(x))


class _Sum(LocalOperator):
    def __init__(self, A, B):
        if A.shape != B.shape:
            raise ValueError(f"shape mismatch {A.shape} + {B.shape}")
        super().__init__(A.dims, A.dimsd, dtype=np.result_type(A.dtype, B.dtype))
        self.A, self.B = A, B

    def _matvec(self, x):
        return self.A._matvec(x) + self.B._matvec(x)

    def _rmatvec(self, x):
        return self.A._rmatvec(x) + self.B._rmatvec(x)


# ------------------------------------------------------------------ bases
class MatrixMult(LocalOperator):
    """Dense GEMM block — feeds the MXU. Analog of ``pylops.MatrixMult``."""

    def __init__(self, A, otherdims: Tuple[int, ...] = (), dtype=None):
        # a host (NumPy) matrix stays on the host until this block is
        # applied on its own: the distributed operators that batch
        # their blocks (MPIBlockDiag, MPIVStack) read ``A_source`` and
        # place each shard on the device that owns it, so no
        # per-block copy ever lands on the default device
        self.A_source = A if isinstance(A, np.ndarray) else jnp.asarray(A)
        A = self.A_source
        self.otherdims = tuple(otherdims)
        nother = int(np.prod(self.otherdims)) if self.otherdims else 1
        dims = (A.shape[1] * nother,)
        dimsd = (A.shape[0] * nother,)
        super().__init__(dims, dimsd, dtype=dtype
                         or jax.dtypes.canonicalize_dtype(A.dtype))

    @property
    def A(self) -> jax.Array:
        """The matrix as a device array (placed on first use — eagerly
        even when that first use is under a trace, so a concrete
        array is cached, never a tracer)."""
        if isinstance(self.A_source, np.ndarray):
            with jax.ensure_compile_time_eval():
                self.A_source = jnp.asarray(self.A_source)
        return self.A_source

    def _matvec(self, x):
        if self.otherdims:
            X = x.reshape(self.A.shape[1], -1)
            return (self.A @ X).ravel()
        return self.A @ x

    def _rmatvec(self, x):
        if self.otherdims:
            X = x.reshape(self.A.shape[0], -1)
            return (self.A.conj().T @ X).ravel()
        return self.A.conj().T @ x


class Identity(LocalOperator):
    def __init__(self, N: int, M: Optional[int] = None, dtype=None):
        M = N if M is None else M
        super().__init__((M,), (N,), dtype=dtype)

    def _matvec(self, x):
        N, M = self.shape
        if M == N:
            return x
        if N < M:
            return x[:N]
        return jnp.pad(x, (0, N - M))

    def _rmatvec(self, x):
        N, M = self.shape
        if M == N:
            return x
        if M < N:
            return x[:M]
        return jnp.pad(x, (0, M - N))


class Diagonal(LocalOperator):
    def __init__(self, diag, dtype=None):
        diag = jnp.asarray(diag).ravel()
        self.diag = diag
        super().__init__((diag.size,), (diag.size,), dtype=dtype or diag.dtype)

    def _matvec(self, x):
        return self.diag * x

    def _rmatvec(self, x):
        return jnp.conj(self.diag) * x


class Zero(LocalOperator):
    def __init__(self, N: int, M: Optional[int] = None, dtype=None):
        M = N if M is None else M
        super().__init__((M,), (N,), dtype=dtype)

    def _matvec(self, x):
        return jnp.zeros(self.shape[0], dtype=x.dtype)

    def _rmatvec(self, x):
        return jnp.zeros(self.shape[1], dtype=x.dtype)


class Transpose(LocalOperator):
    """N-D axes permutation as a flat operator."""

    def __init__(self, dims, axes, dtype=None):
        self.axes = tuple(axes)
        dimsd = tuple(np.asarray(dims)[list(self.axes)])
        self.dims_nd = tuple(dims)
        self.axes_inv = tuple(np.argsort(self.axes))
        super().__init__(dims, dimsd, dtype=dtype)

    def _matvec(self, x):
        return jnp.transpose(x.reshape(self.dims_nd), self.axes).ravel()

    def _rmatvec(self, x):
        return jnp.transpose(x.reshape(self.dimsd), self.axes_inv).ravel()


class Roll(LocalOperator):
    def __init__(self, N: int, shift: int = 1, dtype=None):
        self.shift = shift
        super().__init__((N,), (N,), dtype=dtype)

    def _matvec(self, x):
        return jnp.roll(x, self.shift)

    def _rmatvec(self, x):
        return jnp.roll(x, -self.shift)


class Flip(LocalOperator):
    def __init__(self, N: int, dtype=None):
        super().__init__((N,), (N,), dtype=dtype)

    def _matvec(self, x):
        return jnp.flip(x)

    _rmatvec = _matvec


class Pad(LocalOperator):
    def __init__(self, dims, pad: Sequence[Tuple[int, int]], dtype=None):
        self.dims_nd = tuple(np.atleast_1d(dims))
        self.pad_nd = tuple(tuple(p) for p in np.atleast_2d(pad))
        dimsd = tuple(d + p[0] + p[1] for d, p in zip(self.dims_nd, self.pad_nd))
        self.dimsd_nd = dimsd
        super().__init__(self.dims_nd, dimsd, dtype=dtype)

    def _matvec(self, x):
        return jnp.pad(x.reshape(self.dims_nd), self.pad_nd).ravel()

    def _rmatvec(self, x):
        sl = tuple(slice(p[0], p[0] + d)
                   for d, p in zip(self.dims_nd, self.pad_nd))
        return x.reshape(self.dimsd_nd)[sl].ravel()


class FunctionOperator(LocalOperator):
    def __init__(self, f: Callable, fH: Callable, N: int, M: Optional[int] = None,
                 dtype=None):
        M = N if M is None else M
        self.f, self.fH = f, fH
        super().__init__((M,), (N,), dtype=dtype)

    def _matvec(self, x):
        return self.f(x)

    def _rmatvec(self, x):
        return self.fH(x)


# ------------------------------------------------------- stencil operators
def _deriv_setup(dims, axis, sampling):
    dims = tuple(np.atleast_1d(dims))
    axis = axis % len(dims)
    return dims, axis, sampling


class _AxisStencil(LocalOperator):
    """What the derivative stencils share: slices, zero padding and
    concatenation along ``self.axis`` WHERE IT LIES. A ``moveaxis`` of
    the minor (lane) axis to the front and back was, in the program
    compiled for a v5e, two transposed copies of the array an apply
    (PERF.md section 6, PR 32)."""

    def _sl(self, v, lo, hi=None):
        idx = [slice(None)] * v.ndim
        idx[self.axis] = slice(lo, hi)
        return v[tuple(idx)]

    def _pad0(self, v, before, after):
        padw = [(0, 0)] * v.ndim
        padw[self.axis] = (before, after)
        return jnp.pad(v, padw)

    def _cat(self, *rows):
        return jnp.concatenate(rows, axis=self.axis)


class FirstDerivative(_AxisStencil):
    """Local first derivative, matching pylops' stencils so the
    distributed variant (ref ``basicoperators/FirstDerivative.py``) has a
    bit-exact local building block. ``kind``: forward | backward |
    centered (3- or 5-point; zero rows at the boundary unless ``edge``).

    Implementation note: written entirely with pad/concat arithmetic —
    no ``.at[]`` scatters — because XLA's SPMD partitioner miscompiles
    scatter/dynamic-update-slice ops on sharded operands (observed on
    the CPU backend of jax 0.9; GSPMD is shared with TPU).
    """

    def __init__(self, dims, axis: int = 0, sampling: float = 1.0,
                 kind: str = "centered", edge: bool = False, order: int = 3,
                 dtype=None):
        self.dims_nd, self.axis, self.sampling = _deriv_setup(dims, axis, sampling)
        self.kind, self.edge, self.order = kind, edge, order
        if kind == "centered" and order not in (3, 5):
            raise NotImplementedError("'order' must be 3 or 5")
        super().__init__(self.dims_nd, self.dims_nd, dtype=dtype)

    @_scoped
    def _matvec(self, x):
        v = x.reshape(self.dims_nd)
        s = self.sampling
        n = v.shape[self.axis]
        p, g = self._pad0, self._sl
        if self.kind == "forward":
            y = p((g(v, 1) - g(v, 0, -1)) / s, 0, 1)
        elif self.kind == "backward":
            y = p((g(v, 1) - g(v, 0, -1)) / s, 1, 0)
        elif self.order == 3:
            y = p((g(v, 2) - g(v, 0, -2)) / (2 * s), 1, 1)
            if self.edge:
                y = y + p((g(v, 1, 2) - g(v, 0, 1)) / s, 0, n - 1)
                y = y + p((g(v, -1) - g(v, -2, -1)) / s, n - 1, 0)
        else:  # centered, 5-point: (x[i-2] - 8x[i-1] + 8x[i+1] - x[i+2])/12Δ
            y = p((g(v, 0, -4) - 8 * g(v, 1, -3) + 8 * g(v, 3, -1)
                   - g(v, 4)) / (12 * s), 2, 2)
            if self.edge:
                y = y + p((g(v, 1, 2) - g(v, 0, 1)) / s, 0, n - 1)
                y = y + p((g(v, 2, 3) - g(v, 0, 1)) / (2 * s), 1, n - 2)
                y = y + p((g(v, -1) - g(v, -3, -2)) / (2 * s), n - 2, 1)
                y = y + p((g(v, -1) - g(v, -2, -1)) / s, n - 1, 0)
        return y.ravel()

    @_scoped
    def _rmatvec(self, x):
        v = x.reshape(self.dims_nd)
        s = self.sampling
        n = v.shape[self.axis]
        p, g, cat = self._pad0, self._sl, self._cat
        if self.kind == "forward":
            c = g(v, 0, -1) / s
            y = p(c, 1, 0) - p(c, 0, 1)
        elif self.kind == "backward":
            c = g(v, 1) / s
            y = p(c, 1, 0) - p(c, 0, 1)
        elif self.order == 3:
            c = g(v, 1, -1) / (2 * s)
            y = p(c, 2, 0) - p(c, 0, 2)
            if self.edge:
                v0, v1 = g(v, 0, 1), g(v, -1)
                y = y + p(cat(-v0 / s, v0 / s), 0, n - 2)
                y = y + p(cat(-v1 / s, v1 / s), n - 2, 0)
        else:
            c = g(v, 2, -2) / (12 * s)
            y = p(c, 0, 4) - 8 * p(c, 1, 3) + 8 * p(c, 3, 1) - p(c, 4, 0)
            if self.edge:
                v0, v1, vm2, vm1 = g(v, 0, 1), g(v, 1, 2), g(v, -2, -1), \
                    g(v, -1)
                y = y + p(cat(-v0 / s, v0 / s), 0, n - 2)
                y = y + p(cat(-v1 / (2 * s), jnp.zeros_like(v1),
                              v1 / (2 * s)), 0, n - 3)
                y = y + p(cat(-vm2 / (2 * s), jnp.zeros_like(vm2),
                              vm2 / (2 * s)), n - 3, 0)
                y = y + p(cat(-vm1 / s, vm1 / s), n - 2, 0)
        return y.ravel()


class SecondDerivative(_AxisStencil):
    """3-point second derivative, all three pylops stencil kinds
    (ref ``basicoperators/SecondDerivative.py:78-108`` registers
    forward/centered/backward; ``edge`` affects centered only, as in
    serial pylops). Scatter-free for partitioner safety (see
    FirstDerivative note).

    Global-view stencils (core ``d[i] = x[i] - 2 x[i+1] + x[i+2]``):
    forward places ``d[i]`` at row ``i`` (last two rows zero), backward
    at row ``i+2`` (first two rows zero), centered at row ``i+1`` with
    optional one-sided ``edge`` rows at 0 and n-1."""

    def __init__(self, dims, axis: int = 0, sampling: float = 1.0,
                 kind: str = "centered", edge: bool = False, dtype=None):
        self.dims_nd, self.axis, self.sampling = _deriv_setup(dims, axis, sampling)
        if kind not in ("forward", "backward", "centered"):
            raise NotImplementedError(
                "'kind' must be 'forward', 'centered' or 'backward'")
        self.kind, self.edge = kind, edge
        super().__init__(self.dims_nd, self.dims_nd, dtype=dtype)

    # row offset of the stencil core within the output, per kind
    _CORE_OFFSET = {"forward": (0, 2), "centered": (1, 1), "backward": (2, 0)}

    def _matvec(self, x):
        v = x.reshape(self.dims_nd)
        s2 = self.sampling ** 2
        p, g = self._pad0, self._sl
        n = v.shape[self.axis]
        before, after = self._CORE_OFFSET[self.kind]
        y = p((g(v, 0, -2) - 2 * g(v, 1, -1) + g(v, 2)) / s2, before, after)
        if self.kind == "centered" and self.edge:
            y = y + p((g(v, 0, 1) - 2 * g(v, 1, 2) + g(v, 2, 3)) / s2,
                      0, n - 1)
            y = y + p((g(v, -3, -2) - 2 * g(v, -2, -1) + g(v, -1)) / s2,
                      n - 1, 0)
        return y.ravel()

    def _rmatvec(self, x):
        v = x.reshape(self.dims_nd)
        s2 = self.sampling ** 2
        p, g, cat = self._pad0, self._sl, self._cat
        n = v.shape[self.axis]
        before, after = self._CORE_OFFSET[self.kind]
        # adjoint spreads each output row back over its 3 input columns:
        # c holds the rows carrying the core, shifted to columns 0/1/2
        c = g(v, before, n - after) / s2
        y = p(c, 0, 2) - 2 * p(c, 1, 1) + p(c, 2, 0)
        if self.kind == "centered" and self.edge:
            v0, v1 = g(v, 0, 1), g(v, -1)
            y = y + p(cat(v0, -2 * v0, v0) / s2, 0, n - 3)
            y = y + p(cat(v1, -2 * v1, v1) / s2, n - 3, 0)
        return y.ravel()


class Laplacian(LocalOperator):
    """Weighted sum of second derivatives along ``axes``."""

    def __init__(self, dims, axes=(-2, -1), weights=(1, 1),
                 sampling=(1, 1), dtype=None):
        dims = tuple(np.atleast_1d(dims))
        self.ops = [SecondDerivative(dims, axis=ax, sampling=s, dtype=dtype)
                    for ax, s in zip(axes, sampling)]
        self.weights = tuple(weights)
        super().__init__(dims, dims, dtype=dtype)

    def _matvec(self, x):
        return sum(w * op._matvec(x) for w, op in zip(self.weights, self.ops))

    def _rmatvec(self, x):
        return sum(np.conj(w) * op._rmatvec(x)
                   for w, op in zip(self.weights, self.ops))


# --------------------------------------------------------------- stacking
class VStack(LocalOperator):
    def __init__(self, ops: Sequence[LocalOperator], dtype=None):
        self.ops = list(ops)
        if len({op.shape[1] for op in self.ops}) != 1:
            raise ValueError("column size mismatch in VStack")
        self.nrows = [op.shape[0] for op in self.ops]
        super().__init__((self.ops[0].shape[1],), (sum(self.nrows),),
                         dtype=dtype or np.result_type(*[o.dtype for o in self.ops]))

    def _matvec(self, x):
        return jnp.concatenate([op.matvec(x) for op in self.ops])

    def _rmatvec(self, x):
        out, off = None, 0
        for op, n in zip(self.ops, self.nrows):
            part = op.rmatvec(x[off:off + n])
            out = part if out is None else out + part
            off += n
        return out


class HStack(LocalOperator):
    def __init__(self, ops: Sequence[LocalOperator], dtype=None):
        self.ops = list(ops)
        if len({op.shape[0] for op in self.ops}) != 1:
            raise ValueError("row size mismatch in HStack")
        self.ncols = [op.shape[1] for op in self.ops]
        super().__init__((sum(self.ncols),), (self.ops[0].shape[0],),
                         dtype=dtype or np.result_type(*[o.dtype for o in self.ops]))

    def _matvec(self, x):
        out, off = None, 0
        for op, n in zip(self.ops, self.ncols):
            part = op.matvec(x[off:off + n])
            out = part if out is None else out + part
            off += n
        return out

    def _rmatvec(self, x):
        return jnp.concatenate([op.rmatvec(x) for op in self.ops])


class BlockDiag(LocalOperator):
    def __init__(self, ops: Sequence[LocalOperator], dtype=None):
        self.ops = list(ops)
        self.nrows = [op.shape[0] for op in self.ops]
        self.ncols = [op.shape[1] for op in self.ops]
        super().__init__((sum(self.ncols),), (sum(self.nrows),),
                         dtype=dtype or np.result_type(*[o.dtype for o in self.ops]))

    def _matvec(self, x):
        out, off = [], 0
        for op, n in zip(self.ops, self.ncols):
            out.append(op.matvec(x[off:off + n]))
            off += n
        return jnp.concatenate(out)

    def _rmatvec(self, x):
        out, off = [], 0
        for op, n in zip(self.ops, self.nrows):
            out.append(op.rmatvec(x[off:off + n]))
            off += n
        return jnp.concatenate(out)


# -------------------------------------------------------------- transforms
def truncated_dft_pays(nfft: int, nfkeep: int) -> bool:
    """Whether a real transform of length ``nfft`` of which only the
    first ``nfkeep`` bins are kept is made as ONE real matrix product
    against ``(nfft, 2 nfkeep)`` cosines and sines (yes) or as the
    whole ``jnp.fft`` with the cut beside it (no). A rule in what the
    operator sees, the same on every backend, so the CPU tests run the
    form the chip runs.

    Measured on a TPU v5e (``chip_probe/fft_forms_probe.py``, PR 35;
    ms an apply of one 268 MB f32 vector under ``highest``, forward /
    adjoint, product against ``jnp.fft``-with-the-cut):

    ======  ======  =======  =============  =============
    nfft    nfkeep  traces   product        fft and cut
    ======  ======  =======  =============  =============
    1,023   64      65,536   2.18 / 2.19    15.77 / 26.28
    1,023   128     65,536   2.97 / 2.80    17.61 / 26.47
    1,023   256     65,536   4.94 / 5.17    18.66 / 27.81
    1,023   511     65,536   10.00 / 10.15  20.96 / 29.86
    1,000   256     65,536   4.85 / 5.14    15.40 / 26.44
    1,022   256     65,536   4.88 / 5.13    15.57 / 26.18
    4,095   512     16,384   7.02 / 6.22    51.21 / 82.11
    4,095   2,047   16,384   25.35 / 23.58  54.77 / 85.66
    1,024   64      65,536   2.19 / 2.15    12.33 / 16.07
    1,024   256     65,536   4.86 / 5.19    13.56 / 17.62
    1,024   512     65,536   9.09 / 9.24    15.34 / 19.63
    4,096   64      16,384   2.21 / 2.03    12.09 / 16.44
    4,096   512     16,384   6.94 / 6.17    12.22 / 16.66
    4,096   2,048   16,384   24.37 / 22.91  15.33 / 20.17
    ======  ======  =======  =============  =============

    - a length that is no power of two: XLA's own transform there IS a
      dense DFT product, with every column (its time grows with the
      length: 16–30 ms at 1,000–1,023 samples, 50–86 at 4,095), so the
      product with fewer columns pays for every band cut, up to the
      whole half spectrum less one bin;
    - a power of two: XLA has a real FFT there, 12 ms forward and
      16–20 adjoint whatever the length, while the product's time
      grows with its columns: it pays up to the last band measured to
      win, **512 bins**; 2,048 lost.

    With nothing cut (``nfkeep`` the whole half spectrum) the
    transform is ``jnp.fft``, as it was."""
    if nfkeep >= nfft // 2 + 1:
        return False
    return bool(nfft & (nfft - 1)) or nfkeep <= 512


@functools.lru_cache(maxsize=32)
def _truncated_dft_matrix(nt: int, nfft: int, nfkeep: int, shift: bool,
                          dtype: str) -> np.ndarray:
    """``W = [C | S]``, ``(nt, 2 nfkeep)``: the first ``nfkeep`` bins
    of pylops' isometric real FFT of length ``nfft`` as a real matrix —
    ``C[t, k] = s_k cos(2 pi k t'/nfft) / sqrt(nfft)``, ``S`` the same
    with ``-sin``; ``s_k = sqrt(2)`` on the strictly positive
    non-Nyquist bins, 1 on the others; ``t'`` the place of sample ``t``
    after ``ifftshift`` where ``shift`` is set (a permutation of rows)
    and a sample the transform truncates away a zero row. ``x @ W``
    is the kept spectrum's parts side by side, ``W @ [re; im]`` the
    adjoint: zero-pad, halve the doubled bins, ``irfft``, ``fftshift``
    (bin 0's imaginary part meets a zero column). Made in float64 from
    ``dft``'s planes (phases reduced as integers), then rounded."""
    Fr, Fi, _ = dft._dft_mat_planar_np(nfft, -1.0, "float64", nfkeep)
    k = np.arange(nfkeep)
    s = np.where((k >= 1) & (k < (nfft + 1) // 2), np.sqrt(2.0), 1.0) \
        / np.sqrt(nfft)
    W = np.zeros((nt, 2 * nfkeep))
    rows = min(nt, nfft)
    W[:rows] = np.concatenate([Fr[:rows] * s, Fi[:rows] * s], axis=1)
    if shift:
        W = np.fft.fftshift(W, axes=0)
    return W.astype(dtype)


class FFT(LocalOperator):
    """1-D (real-input) FFT along an axis of an N-D layout, with the
    norm/scaling conventions pylops uses: ``norm="ortho"`` plus, for
    ``real=True``, the √2 scaling of strictly-positive non-Nyquist
    frequencies that makes the half-spectrum operator an isometry (and
    its adjoint pass the dot test) — the same convention the reference's
    distributed FFT preserves (ref ``signalprocessing/FFTND.py:278-309``).

    ``planes=True`` (requires ``real=True``): the half-spectrum leaves
    as a STACKED REAL plane pair — data layout ``(2,) + dimsd`` with
    ``[0]`` the real and ``[1]`` the imaginary plane, operator dtype
    the real plane dtype — so no complex dtype ever reaches the device.
    This is the local transform of the planar MDC chain
    (``ops/mdc.py``) on TPU runtimes without complex lowering.

    ``nfkeep`` (requires ``real=True``; ``MPIMDC`` sets it from its
    ``nfmax``, a user has no reason to): only the first ``nfkeep`` bins
    of the half spectrum are the operator's output — the transform
    followed by the frequency cut, as one operator whose adjoint
    zero-pads. Where :func:`truncated_dft_pays` says so an apply is
    then ONE real matrix product at the package's matmul precision
    against ``W = [C | S]`` of ``(nt, 2 nfkeep)`` cosines and sines
    (``_truncated_dft_matrix``: the ``ifftshift``, the ortho norm and
    the √2 are in the matrix), the adjoint the product with the same
    matrix transposed, and both engines share it: the complex one gets
    ``lax.complex`` of the product's two halves, the planar one the
    halves as they are. Elsewhere (nothing cut, or the rule's other
    side) the apply is ``jnp.fft`` (``dft``) with the cut and the pad
    beside it. ``fft.path_select`` (``form`` = ``truncated_dft`` or
    ``fft``, ``nt``, ``nfft``, ``nfkeep``, ``traces``, ``adjoint`` and,
    for ``fft``, a one-word ``why``: ``complex``, ``uncut``, or
    ``wide`` — a band of a power-of-two length beyond the rule)
    says what a traced apply took under ``PYLOPS_MPI_TPU_TRACE``."""

    def __init__(self, dims, axis: int = 0, nfft: Optional[int] = None,
                 real: bool = True, ifftshift_before: bool = False,
                 dtype=None, planes: bool = False,
                 nfkeep: Optional[int] = None):
        dims = tuple(np.atleast_1d(dims))
        self.dims_nd = dims
        self.axis = axis % len(dims)
        self.nfft = nfft or dims[self.axis]
        self.real = real
        self.planes = bool(planes)
        if self.planes and not real:
            raise ValueError("planes=True requires real=True (the "
                             "plane-pair half-spectrum layout)")
        self.ifftshift_before = bool(ifftshift_before)
        nf = self.nfft // 2 + 1 if real else self.nfft
        self.nfkeep = nf if nfkeep is None else int(nfkeep)
        if not 1 <= self.nfkeep <= nf or (self.nfkeep < nf and not real):
            raise ValueError(f"nfkeep must keep 1 to {nf} leading bins of "
                             f"a real transform's half spectrum, got "
                             f"{nfkeep!r} (real={real})")
        dimsd = list(dims)
        dimsd[self.axis] = self.nfkeep
        self.dimsd_nd = tuple(dimsd)
        # bins 1..nf-1 except the Nyquist bin of an even nfft
        self._double_hi = nf - 1 if self.nfft % 2 == 0 else nf
        # the apply works on the array with the axes before and after
        # the transform's each folded into one (and left out where it
        # is 1): the flat vector is the same, and a short minor axis —
        # MDC's ``(nt, nr, nv)`` with ``nv`` = 16 — is not padded to the
        # 128 lanes of a TPU tile, eight times the array (compiled for
        # a v5e, PR 34: 1.07 GB a 134 MB plane)
        pre = int(np.prod(dims[:self.axis], dtype=np.int64))
        post = int(np.prod(dims[self.axis + 1:], dtype=np.int64))
        fold = lambda n: tuple(d for d, keep in (
            (pre, pre > 1), (n, True), (post, post > 1)) if keep)
        self._cdims, self._cdimsd = fold(dims[self.axis]), fold(self.nfkeep)
        self._caxis = int(pre > 1)
        self._traces = pre * post
        small = np.dtype(dtype or "float32").itemsize == 4
        rdt = np.float32 if small else np.float64
        # why an apply is jnp.fft, or None where it is the one product
        if not real:
            self._why = "complex"
        elif self.nfkeep == nf:
            self._why = "uncut"
        elif not truncated_dft_pays(self.nfft, self.nfkeep):
            self._why = "wide"
        else:
            self._why = None
        self._W = None if self._why else _truncated_dft_matrix(
            dims[self.axis], self.nfft, self.nfkeep, self.ifftshift_before,
            np.dtype(rdt).name)
        if self.planes:
            super().__init__(dims, (2,) + self.dimsd_nd, dtype=rdt)
            return
        super().__init__(dims, self.dimsd_nd,
                         dtype=np.complex64 if small else np.complex128)

    def _select(self, adjoint: bool):
        _trace.event("fft.path_select", cat="schedule",
                     form="fft" if self._why else "truncated_dft",
                     nt=self._cdims[self._caxis], nfft=self.nfft,
                     nfkeep=self.nfkeep, traces=self._traces,
                     adjoint=int(adjoint),
                     **({"why": self._why} if self._why else {}))
        return self._why is None

    def _scale_pos(self, y, factor):
        # mask-multiply, not .at[].multiply: scatter ops miscompile under
        # the SPMD partitioner on sharded operands
        nf = y.shape[self._caxis]
        ar = jnp.arange(nf)
        # a Python float: weakly typed, y keeps its dtype under x64
        fac = jnp.where((ar >= 1) & (ar < self._double_hi), float(factor),
                        1.0)
        shape = [1] * len(self._cdimsd)
        shape[self._caxis] = nf
        return y * fac.reshape(shape)

    def _cut(self, y, n):
        """The first ``n`` entries along the transform's axis."""
        return jax.lax.slice_in_dim(y, 0, n, axis=self._caxis)

    def _pad_bins(self, v):
        """Zeros for the bins that were cut away (the cut's adjoint)."""
        pad = [(0, 0)] * v.ndim
        pad[self._caxis] = (0, self.nfft // 2 + 1 - self.nfkeep)
        return jnp.pad(v, pad)

    def _product(self, v, over: int):
        """``W`` contracted over its axis ``over`` (0: the samples,
        forward; 1: the parts' bins, adjoint) with ``v``'s transform
        axis, the result's new axis put where that one was."""
        ax = self._caxis
        y = jnp.tensordot(jnp.asarray(self._W), v, axes=((over,), (ax,)))
        return jnp.moveaxis(y, 0, ax)

    @_scoped
    def _matvec(self, x):
        v = x.reshape(self._cdims)
        ax = self._caxis
        if self._select(adjoint=False):
            # (..., 2 nfkeep, ...): the real parts, then the imaginary
            y = self._product(jnp.real(v), 0)
            y = jnp.moveaxis(y.reshape(
                y.shape[:ax] + (2, self.nfkeep) + y.shape[ax + 1:]), ax, 0)
            if self.planes:
                return y.astype(self.dtype).ravel()
            return jax.lax.complex(y[0], y[1]).ravel()
        if self.ifftshift_before:
            v = jnp.fft.ifftshift(v, axes=ax)
        if self.planes:
            yr, yi = dft.rfft_planes(v, n=self.nfft, axis=ax, norm="ortho")
            yr = self._scale_pos(self._cut(yr, self.nfkeep), np.sqrt(2.0))
            yi = self._scale_pos(self._cut(yi, self.nfkeep), np.sqrt(2.0))
            return jnp.stack([yr, yi]).astype(self.dtype).ravel()
        if self.real:
            y = dft.rfft(v.real, n=self.nfft, axis=ax, norm="ortho")
            y = self._scale_pos(self._cut(y, self.nfkeep), np.sqrt(2.0))
        else:
            y = dft.fft(v, n=self.nfft, axis=ax, norm="ortho")
        return y.ravel()

    @_scoped
    def _rmatvec(self, x):
        ax = self._caxis
        if self._select(adjoint=True):
            if self.planes:
                v = x.reshape((2,) + self._cdimsd)
            else:
                v = x.reshape(self._cdimsd)
                v = jnp.stack([v.real, v.imag])
            v = jnp.moveaxis(v, 0, ax)
            v = v.reshape(v.shape[:ax] + (2 * self.nfkeep,)
                          + v.shape[ax + 2:])
            y = self._product(v, 1)
            return y.astype(self.dtype).ravel() if self.planes else y.ravel()
        if self.planes:
            v = x.reshape((2,) + self._cdimsd)
            vr = self._pad_bins(self._scale_pos(v[0], 1.0 / np.sqrt(2.0)))
            vi = self._pad_bins(self._scale_pos(v[1], 1.0 / np.sqrt(2.0)))
            y = dft.irfft_planes(vr, vi, n=self.nfft, axis=ax, norm="ortho")
        else:
            v = x.reshape(self._cdimsd)
            if self.real:
                # adjoint of (√2-scaled) rfft: halve the doubled bins and
                # let irfft's Hermitian extension supply the other half
                v = self._pad_bins(self._scale_pos(v, 1.0 / np.sqrt(2.0)))
                y = dft.irfft(v, n=self.nfft, axis=ax, norm="ortho")
            else:
                y = dft.ifft(v, n=self.nfft, axis=ax, norm="ortho")
        y = self._cut(y, self._cdims[ax])
        if self.ifftshift_before:
            y = jnp.fft.fftshift(y, axes=ax)
        return y.astype(self.dtype).ravel() if self.planes else y.ravel()


class Conv1D(LocalOperator):
    """Stationary 1-D convolution along ``axis`` (zero-phase placement via
    ``offset``), the local building block for deconvolution models:
    ``y[i] = sum_j h[j] x[i + offset - j]``, zero outside the array.

    Applied as a block-Toeplitz product that streams the array once, by
    ONE form on every backend: the Pallas kernel ``pmt_conv1d``
    (``pallas_kernels.conv1d_toeplitz``; compiled on a TPU, interpreted
    elsewhere, as ``pmt_normal`` is). The axis is cut into tiles of
    ``L = 128 * ceil((nh - 1) / 128)`` samples (``conv1d_tile``), so an
    output tile reads its own input tile and the one on either side,
    each through one ``L x L`` Toeplitz block of the filter. f32
    products are ``highest``-equivalent (six bf16 products by hand);
    wider dtypes, interpreted only, take plain dots.

    Live in an apply: the input and the output, **2 arrays**; an axis
    that is no multiple of ``L`` is zero-padded up to one and the
    result cut back, **4 arrays**. Complex data or a complex filter
    pass as their real and imaginary parts stacked on rows (one kernel
    call a part of the filter). ``conv1d.path_select`` (``taps``,
    ``n``, ``form``, ``pad``) says what was traced under
    ``PYLOPS_MPI_TPU_TRACE``. The gather form this replaces held ``nh``
    copies of the array."""

    def __init__(self, dims, h, axis: int = 0, offset: int = 0, dtype=None):
        dims = tuple(np.atleast_1d(dims))
        self.dims_nd = dims
        self.axis = axis % len(dims)
        self.h = jnp.asarray(h)
        self.offset = offset
        super().__init__(dims, dims, dtype=dtype or self.h.dtype)

    @staticmethod
    def _blocks(h, offset, L):
        """``[T_-1; T_0; T_+1]`` stacked on rows, ``(3L, L)``:
        ``T_d[a, b] = h[b + offset - a - d * L]`` (zero outside the
        filter) is the block through which sample ``a`` of input tile
        ``k + d`` reaches sample ``b`` of output tile ``k``."""
        nh = h.shape[0]
        a = jnp.arange(3 * L)[:, None]          # row a of block d: d*L + a
        b = jnp.arange(L)[None, :]
        j = b + offset - a + L                  # = b + offset - a' - d*L
        return jnp.where((j >= 0) & (j < nh), h[jnp.clip(j, 0, nh - 1)], 0)

    def _conv(self, x, h, offset):
        from . import pallas_kernels as pk
        n = self.dims_nd[self.axis]
        v = jnp.moveaxis(x.reshape(self.dims_nd), self.axis, -1)
        v2 = v.reshape(-1, n)
        rows = v2.shape[0]
        nh = h.shape[0]
        L = pk.conv1d_tile(nh)
        pad = -n % L
        _trace.event("conv1d.path_select", cat="schedule", taps=nh, n=n,
                     form="pmt_conv1d", pad=pad)
        cplx = jnp.iscomplexobj(v2) or jnp.iscomplexobj(h)
        if cplx:            # real and imaginary parts stacked on rows
            v2 = jnp.concatenate([v2.real, v2.imag])
        if pad:
            v2 = jnp.pad(v2, ((0, 0), (0, pad)))
        real = v2.dtype

        def through(hpart):
            T = self._blocks(hpart.astype(real), offset, L)
            return pk.conv1d_toeplitz(v2, T)[:, :n]

        if not cplx:
            y = through(h)
        else:
            yr = through(h.real)
            y = yr[:rows] + 1j * yr[rows:]
            if jnp.iscomplexobj(h):
                yi = through(h.imag)
                y = y - yi[rows:] + 1j * yi[:rows]
        return jnp.moveaxis(y.reshape(v.shape), -1, self.axis).ravel()

    @_scoped
    def _matvec(self, x):
        return self._conv(x, self.h, self.offset)

    @_scoped
    def _rmatvec(self, x):
        # correlation = convolution with reversed conj filter, mirrored offset
        h = jnp.flip(jnp.conj(self.h))
        return self._conv(x, h, self.h.shape[0] - 1 - self.offset)


class NonStationaryConvolve1D(LocalOperator):
    """1-D non-stationary convolution with a bank of compact filters
    defined on a coarse grid and linearly interpolated per sample
    (jnp-native analog of ``pylops.signalprocessing.NonStationaryConvolve1D``,
    the rank-local building block of the reference's distributed factory,
    ref ``pylops_mpi/signalprocessing/NonStatConvolve1d.py:139-188``).

    Forward spreads each input sample through its interpolated filter:
    ``y[i-nh//2+j] += hs_i[j] * x[i]``; adjoint gathers.
    """

    def __init__(self, dims, hs, ih, axis: int = -1, dtype=None):
        dims = tuple(np.atleast_1d(dims))
        self.dims_nd = dims
        self.axis = axis % len(dims)
        hs = jnp.asarray(hs)
        ih = np.asarray(ih)
        if hs.shape[1] % 2 == 0:
            raise ValueError("filters hs must have odd length")
        if len(np.unique(np.diff(ih))) > 1:
            raise ValueError(
                "the indices of filters 'ih' are must be regularly sampled")
        self.hs, self.ih = hs, ih
        self.nh = int(hs.shape[1])
        n = dims[self.axis]
        # static per-sample interpolated filter bank (n, nh): nearest
        # filter outside [ih[0], ih[-1]], linear blend inside
        pos = np.arange(n, dtype=float)
        dh = float(ih[1] - ih[0]) if len(ih) > 1 else 1.0
        q = (pos - ih[0]) / dh
        i0 = np.clip(np.floor(q).astype(int), 0, len(ih) - 2 if len(ih) > 1 else 0)
        w = np.clip(q - i0, 0.0, 1.0)[:, None]
        if len(ih) > 1:
            self.Hbank = hs[i0] * (1 - w) + hs[i0 + 1] * w
        else:
            self.Hbank = jnp.broadcast_to(hs[0], (n, self.nh))
        super().__init__(dims, dims, dtype=dtype or hs.dtype)

    def _batched(self, x):
        v = jnp.moveaxis(x.reshape(self.dims_nd), self.axis, -1)
        return v.reshape(-1, self.dims_nd[self.axis]), v.shape

    def _unbatch(self, y2, shp):
        return jnp.moveaxis(y2.reshape(shp), -1, self.axis).ravel()

    def _matvec(self, x):
        v2, shp = self._batched(x)
        n = v2.shape[1]
        half = self.nh // 2
        # pad-and-sum formulation (scatter-free, see FirstDerivative note)
        ypad = sum(
            jnp.pad(v2 * self.Hbank[:, j], ((0, 0), (j, self.nh - 1 - j)))
            for j in range(self.nh))
        return self._unbatch(ypad[:, half:half + n], shp)

    def _rmatvec(self, x):
        v2, shp = self._batched(x)
        n = v2.shape[1]
        half = self.nh // 2
        vpad = jnp.pad(v2, ((0, 0), (half, half)))
        out = jnp.zeros_like(v2)
        for j in range(self.nh):
            out = out + jnp.conj(self.Hbank[:, j]) * vpad[:, j:j + n]
        return self._unbatch(out, shp)


# local operators as pytree nodes: what a registered distributed
# operator composed over them needs to travel into jit as an argument
# (linearoperator.operator_is_jit_arg). The product exposes its
# factors; Conv1D holds only its few taps, which stay with the
# instance. A local operator that owns device arrays registers them
# where it is defined (models/lsm.py::TravelTimeSpray).
from ..linearoperator import register_operator_arrays  # noqa: E402
register_operator_arrays(_Product, "A", "B")
register_operator_arrays(Conv1D)
