"""Post-stack seismic inversion pipeline.

Application-layer analog of the reference's ``tutorials/poststack.py``
(BASELINE config #4): distributed post-stack modelling as an
``MPIBlockDiag`` of per-trace-block local operators, inverted with CGLS,
optionally with Laplacian regularization through a stacked system.

Layout: the model/data cube is ``(nx, nt0)`` or, as in the reference
tutorial, ``(ny, nx, nt0)`` — the distributed spatial axis first, time
last — so each shard's block is contiguous in the global C-order
flatten and the BlockDiag model space coincides with the Laplacian's
(the same reason the reference distributes its model over axis 0,
``tutorials/poststack.py``).

The local modelling operator mirrors pylops' ``PoststackLinearModelling``:
``d = 0.5 · W · D m`` with ``W`` a stationary wavelet convolution along
time and ``D`` the first derivative along time.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp

from ..distributedarray import DistributedArray, Partition
from ..stacked import StackedDistributedArray
from ..ops.blockdiag import MPIBlockDiag
from ..ops.stack import MPIStackedVStack
from ..ops.derivatives import MPILaplacian
from ..ops.local import Conv1D, FirstDerivative, LocalOperator
from ..solvers.basic import cgls

__all__ = ["PoststackLinearModelling", "MPIPoststackLinearModelling",
           "poststack_regularized", "poststack_inversion", "ricker"]


def ricker(t, f0: float = 20.0):
    """Ricker wavelet (zero-phase), the standard seismic test wavelet."""
    t = np.asarray(t)
    t = np.concatenate([-t[:0:-1], t])
    w = (1 - 2 * (np.pi * f0 * t) ** 2) * np.exp(-(np.pi * f0 * t) ** 2)
    return w, t


def PoststackLinearModelling(wav: np.ndarray, nt0: int,
                             spatdims: Tuple[int, ...] = (),
                             dtype=np.float64) -> LocalOperator:
    """Local post-stack modelling ``0.5 · W · D`` over a
    ``(*spatdims, nt0)`` block, time on the last axis (jnp analog of
    ``pylops.avo.poststack.PoststackLinearModelling``)."""
    dims = tuple(spatdims) + (nt0,)
    taxis = len(dims) - 1
    D = FirstDerivative(dims, axis=taxis, kind="centered", edge=True,
                        dtype=dtype)
    W = Conv1D(dims, jnp.asarray(wav), axis=taxis, offset=len(wav) // 2,
               dtype=dtype)
    # a scalar of the operator's dtype, on the right (see
    # ``poststack_regularized``): a float would make an f32 operator
    # call itself f64
    return (W @ D) * np.dtype(dtype).type(0.5)


def _spatdims(spatdims) -> Tuple[int, ...]:
    return tuple(int(n) for n in np.atleast_1d(spatdims))


def MPIPoststackLinearModelling(wav: np.ndarray, nt0: int, spatdims,
                                mesh=None, dtype=np.float64
                                ) -> MPIBlockDiag:
    """Distributed post-stack modelling over a ``(*spatdims, nt0)``
    cube, time last: ``spatdims`` is ``nx`` (2-D) or ``(ny, nx)`` (3-D,
    the reference tutorial's cube), its first axis split over the mesh,
    one local modelling block per shard (the tutorial's ``MPIBlockDiag``
    of one ``PoststackLinearModelling(wav, nt0, spatdims=(ny_i, nx))``
    a rank)."""
    from ..parallel.mesh import default_mesh
    mesh = mesh if mesh is not None else default_mesh()
    nsh = int(mesh.devices.size)
    first, *rest = _spatdims(spatdims)
    chunks = [len(c) for c in np.array_split(np.arange(first), nsh)]
    ops = [PoststackLinearModelling(wav, nt0, (c, *rest), dtype=dtype)
           for c in chunks]
    return MPIBlockDiag(ops, mesh=mesh)


def _stacked(wav, nt0, spatdims, weight, mesh, dtype):
    """``(StackOp, Op, LapOp)`` with ``StackOp = [Op; weight * LapOp]``."""
    Op = MPIPoststackLinearModelling(wav, nt0, spatdims, mesh=mesh,
                                     dtype=dtype)
    dims = _spatdims(spatdims) + (nt0,)
    ones = (1,) * len(dims)
    LapOp = MPILaplacian(dims=dims, axes=tuple(range(len(dims))),
                         weights=ones, sampling=ones, mesh=Op.mesh,
                         dtype=dtype)
    # a scalar of the operator's dtype, on the right (NumPy hands its
    # own scalars to ``__rmul__`` as Python floats): a float would
    # promote the regulariser's half of an f32 system to f64 under x64
    return (MPIStackedVStack([Op, LapOp * np.dtype(dtype).type(weight)]),
            Op, LapOp)


def poststack_regularized(wav: np.ndarray, nt0: int, spatdims,
                          epsR: float, mesh=None, dtype=np.float64):
    """The reference tutorial's regularised system over a
    ``(*spatdims, nt0)`` cube: ``MPIStackedVStack([Op, sqrt(epsR) *
    LapOp])`` with ``Op`` the distributed modelling and ``LapOp`` the
    Laplacian over every axis of the cube (unit weights and sampling).
    Returns ``(StackOp, Op, LapOp)``; the data of ``StackOp`` is
    ``StackedDistributedArray([d, 0])``. The tutorial's convention:
    ``epsR`` weights the regulariser's SQUARED norm
    (:func:`poststack_inversion`'s ``epsR`` is the weight itself)."""
    return _stacked(wav, nt0, spatdims, np.sqrt(epsR), mesh, dtype)


def poststack_inversion(d: np.ndarray, wav: np.ndarray,
                        niter: int = 100, epsR: Optional[float] = None,
                        damp: float = 1e-4, mesh=None, dtype=np.float64,
                        x0: Optional[np.ndarray] = None):
    """Invert post-stack data ``d`` — ``(nx, nt0)`` or ``(ny, nx, nt0)``,
    first axis distributed, time last — for acoustic impedance,
    starting from the background model ``x0`` (``d``'s shape; zero when
    not given).

    ``epsR=None``: plain CGLS. With ``epsR``: Laplacian-regularized
    stacked system ``[Op; epsR·∇²] m = [d; 0]`` — the reference
    tutorial's regularized path via MPIStackedVStack +
    StackedDistributedArray. ``epsR`` here is the WEIGHT on the
    Laplacian, as in pylops' ``PoststackInversion``;
    :func:`poststack_regularized`, the tutorial's hand-built stack,
    takes the tutorial's ``epsR`` and weights by its root.
    """
    *spatdims, nt0 = d.shape
    if epsR is None:
        Op = MPIPoststackLinearModelling(wav, nt0, spatdims, mesh=mesh,
                                         dtype=dtype)
    else:
        StackOp, Op, LapOp = _stacked(wav, nt0, spatdims, epsR, mesh,
                                      dtype)
    dy = DistributedArray.to_dist(np.asarray(d, dtype=dtype).ravel(),
                                  mesh=Op.mesh,
                                  local_shapes=Op.local_shapes_n)
    if x0 is None:
        x0 = DistributedArray(global_shape=Op.shape[1], mesh=Op.mesh,
                              local_shapes=Op.local_shapes_m, dtype=dtype)
    else:
        x0 = DistributedArray.to_dist(
            np.asarray(x0, dtype=dtype).ravel(), mesh=Op.mesh,
            local_shapes=Op.local_shapes_m)
    if epsR is None:
        # damping stabilises the near-singular W·D normal equations
        # (cond ~ 1e17): without it CGLS trajectories are rounding-order
        # sensitive
        x, *_ = cgls(Op, dy, x0, niter=niter, damp=damp, tol=1e-10)
    else:
        zero = DistributedArray(global_shape=LapOp.shape[0], mesh=Op.mesh,
                                dtype=dtype)
        dstack = StackedDistributedArray([dy, zero])
        x, *_ = cgls(StackOp, dstack, x0, niter=niter, damp=damp, tol=1e-10)
    return x.asarray().reshape(d.shape), Op
