"""Multi-dimensional deconvolution (MDD) pipeline.

Application-layer analog of the reference's ``tutorials/mdd.py``
(BASELINE config #5): build the frequency-sharded MDC operator from a
time-domain kernel, model data, and invert with CGLS.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp

from ..distributedarray import DistributedArray, Partition
from ..ops.mdc import MPIMDC
from ..solvers.basic import cgls

__all__ = ["mdd", "kernel_to_frequency"]


def kernel_to_frequency(Gt: np.ndarray, nfmax: Optional[int] = None
                        ) -> np.ndarray:
    """Time-domain kernel ``(ns, nr, nt)`` → one-sided frequency kernel
    ``(nfmax, ns, nr)`` (the preprocessing step of tutorials/mdd.py).
    A host-side helper for tutorial-sized kernels (NumPy in, NumPy
    out): a kernel at survey scale is made, or loaded, on the device
    in the frequency domain and handed to :func:`mdd` as it is."""
    ns, nr, nt = Gt.shape
    Gf = np.fft.rfft(Gt, nt, axis=-1)
    Gf = np.moveaxis(Gf, -1, 0)          # (nfft, ns, nr)
    if nfmax is not None:
        Gf = Gf[:nfmax]
    return Gf


def mdd(G, d, nt: int, nv: int = 1, dt: float = 1.0, dr: float = 1.0,
        twosided: bool = True, niter: int = 50, tol: float = 1e-10,
        mesh=None) -> Tuple[np.ndarray, object]:
    """Solve ``d = MDC(G) m`` for ``m`` with CGLS from a zero start
    (upstream ``tutorials/mdd.py``: ``cgls(MDCop, d, x0=zeros,
    niter=50, tol=1e-10)``).

    Parameters
    ----------
    G : (nfmax, ns, nr) complex frequency kernel, host or device; a
        device array is stored by the operator as itself
        (:func:`MPIMDC`: the kernel is held once)
    d : (nt, ns, nv) data, host or device

    The data and the zero start are ``Partition.BROADCAST`` vectors in
    the operator's own real dtype (float32 for a complex64 kernel):
    nothing is widened on the host, and a device ``d`` never leaves the
    device. Returns the model ``(nt, nr, nv)`` on the host and the
    operator. The benchmark's cell ``mdd_obc.cgls_nv16`` runs these
    lines with the operator built once
    (``chipbench/loops/closed_broadcast.py``).
    """
    Op = MPIMDC(G, nt=nt, nv=nv, dt=dt, dr=dr, twosided=twosided, mesh=mesh)
    dy = DistributedArray(global_shape=Op.shape[0], mesh=mesh,
                          partition=Partition.BROADCAST, dtype=Op.dtype)
    dy[:] = jnp.asarray(d, dtype=Op.dtype).ravel()
    x0 = DistributedArray(global_shape=Op.shape[1], mesh=mesh,
                          partition=Partition.BROADCAST, dtype=Op.dtype)
    x = cgls(Op, dy, x0=x0, niter=niter, tol=tol)[0]
    nr = Op.shape[1] // (nt * nv)
    return x.asarray().reshape(nt, nr, nv), Op
