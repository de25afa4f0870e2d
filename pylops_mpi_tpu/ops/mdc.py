"""Multi-dimensional convolution (MDC).

Rebuild of ``pylops_mpi/waveeqprocessing/MDC.py:12-180``. The
reference's lazy chain is ``F1ᴴ · I1ᴴ · Fredholm1 · I · F`` — real FFTs
along time of the replicated model/data (wrapped local operators, ref
``MDC.py:55-58``), ``I``/``I1`` cutting to the first ``nfmax``
frequencies, the frequency-sharded :class:`MPIFredholm1` the
distributed core. Here it is THREE operators, ``F1ᴴ · Fredholm1 · F``:
the local transform makes only the bins the product keeps
(``local.FFT(nfkeep=nfmax)``: with fewer kept than the half spectrum
has, one real matrix product against ``(nt, 2 nfmax)`` cosines and
sines where ``local.truncated_dft_pays`` says so, forward and
adjoint; PERF.md section 6, PR 35), so no full half spectrum is made
to be thrown away and no cut or zero pad stands beside the transform.
The reference prescales the kernel by ``dr·dt·√nt`` (ref
``MDC.py:37-43``) — a second array of the kernel's size; here the
factor rides on the SPECTRUM the Fredholm product returns (the chain
is linear: ``(αG) m = α (G m)``), so the kernel is held once, as
:class:`MPIFredholm1` stores it: a complex kernel as its real
(re, im) plane pair, which may be handed over as that pair — a real
``(2, nfmax, ns, nr)`` device array is kept as itself.

Engines — they differ only in how the spectrum is carried between the
three operators: ``complex`` (the default wherever the runtime lowers
complex dtypes, a v5e included; PERF.md section 6, PR 34) as complex
``(nfmax, ·, nv)`` vectors, the reference layout; ``planar``
(auto-selected when the resolved local-FFT mode is ``planar``, i.e. on
TPU runtimes with no complex lowering at all, ``ops/dft.py``) as a
STACKED REAL plane pair ``(2, nfmax, ·, nv)`` that
``MPIFredholm1(planar=True)`` contracts against the kernel's stored
planes — so the compiled end-to-end MDC program contains no complex
dtype anywhere. Model and data are real time-domain vectors on both
ends in either engine; shapes and numerics match to plane precision.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np
import jax.numpy as jnp

from ..diagnostics import trace as _trace
from ..distributedarray import DistributedArray
from ..linearoperator import (MPILinearOperator, _ProductLinearOperator,
                              _ScaledLinearOperator, aslinearoperator,
                              register_operator_arrays)
from . import dft
from .fredholm import MPIFredholm1
from .local import FFT as _LocalFFT

__all__ = ["MPIMDC"]


def MPIMDC(G, nt: int, nv: int, nfreq: Optional[int] = None, dt: float = 1.0,
           dr: float = 1.0, twosided: bool = True, saveGt: bool = True,
           conj: bool = False, prescaled: bool = False, mesh=None,
           compute_dtype=None,
           engine: Optional[str] = None) -> MPILinearOperator:
    """Distributed MDC operator (ref ``MDC.py:82-180``). ``G`` is the
    full frequency-domain kernel ``(nfmax, ns, nr)`` (one controller —
    the reference passes each rank its frequency chunk).

    **The kernel is held once**: ``G`` is neither scaled (the factor
    ``dr·dt·√nt`` multiplies the Fredholm product's spectrum;
    ``prescaled=True`` leaves it out) nor conjugate-transposed
    (``saveGt`` is accepted for the reference's sake and has no
    effect: :class:`MPIFredholm1`'s adjoint contracts the other axis
    of the stored kernel). ``G`` is upstream's complex
    ``(nfmax, ns, nr)`` — split into planes once, at construction — or
    the plane pair itself, real ``(2, nfmax, ns, nr)``, which a device
    array of the right dtype stays: the form for a kernel that fills
    the chip (8.59 GB at ocean-bottom scale: a complex64 argument
    costs its own size again in every program on a TPU; PERF.md
    section 6, PR 34).

    **The chain is three operators**, ``F1ᴴ · Fredholm1 · F``: ``F``
    and ``F1`` are ``local.FFT(nfkeep=nfmax)``, which make the
    ``nfmax`` bins the product keeps and no others — with
    ``nfmax < nfft`` one real matrix product against ``(nt, 2 nfmax)``
    cosines and sines, forward and adjoint, where
    ``local.truncated_dft_pays`` says so; with nothing cut, ``jnp.fft``
    as before (``fft.path_select`` says which).

    ``compute_dtype`` (e.g. ``jnp.complex64``) narrows the stored
    kernel — the operator's memory hog — via
    ``MPIFredholm1(compute_dtype=...)``; transforms and vectors keep
    the operator dtype. ``engine``: ``"complex"`` | ``"planar"`` |
    None — the two differ only in how the spectrum is carried between
    the three operators (complex ``(nfmax, ·, nv)`` vectors, or their
    real (re, im) planes stacked); one product makes it for both. None
    is a rule in what the operator sees: ``complex`` (inside a program
    a complex array IS a pair of real ones on a TPU, at no cost)
    wherever the runtime lowers complex dtypes — every CPU, and the
    v5e, where the two lay 2 % apart at ocean-bottom scale (PERF.md
    section 6, PR 34) — and ``planar`` exactly when
    ``dft.resolved_mode() == "planar"`` (a runtime with no complex
    lowering, where nothing else runs). ``mdc.engine_select``
    (``engine``, ``nfmax``, ``ns``, ``nr``, ``nv``, ``kernel_bytes``,
    a one-word ``why``: ``kwarg``, ``fft_mode`` or ``complex_lowers``)
    says what was built under ``PYLOPS_MPI_TPU_TRACE``. Both engines
    expose identical external shapes/dtypes (real model in, real data
    out)."""
    if not hasattr(G, "ndim"):
        G = np.asarray(G)
    if twosided and nt % 2 == 0:
        raise ValueError("nt must be odd number")
    if engine not in ("complex", "planar", None):
        raise ValueError(f"engine must be 'complex', 'planar' or None, "
                         f"got {engine!r}")
    why = "kwarg"
    if engine is None:
        engine = "planar" if dft.resolved_mode() == "planar" else "complex"
        why = "fft_mode" if engine == "planar" else "complex_lowers"
    # a real 4-D G is the complex kernel's plane pair (MPIFredholm1)
    dtype = np.result_type(G.dtype, np.complex64)
    rdtype = np.real(np.ones(1, dtype=dtype)).dtype
    nfmax, ns, nr = G.shape[-3:]
    nfft = int(np.ceil((nt + 1) / 2))
    nfmax_req = nfmax if nfreq is None else nfreq
    if nfmax_req > nfft:
        nfmax_req = nfft
        logging.warning("nfmax set equal to ceil[(nt+1)/2]=%d" % nfft)
    if nfmax_req != nfmax:
        G = G[..., :nfmax_req, :, :]
        nfmax = nfmax_req

    _trace.event("mdc.engine_select", cat="schedule", engine=engine,
                 nfmax=nfmax, ns=ns, nr=nr, nv=nv,
                 kernel_bytes=nfmax * ns * nr * dtype.itemsize, why=why)

    planar = engine == "planar"
    if planar:
        # conj folds into the stored kernel: Fredholm1.conj() == the
        # operator with kernel conj(G) (the _ConjLinearOperator wrapper
        # conjugates vectors, which is an identity on real planes and
        # would silently do nothing here)
        Gk = G if not conj else jnp.conj(G) if G.ndim == 3 \
            else jnp.stack([G[0], -G[1]])
        Frop = MPIFredholm1(Gk, nv, saveGt=saveGt, mesh=mesh,
                            dtype=rdtype, compute_dtype=compute_dtype,
                            planar=True)
    else:
        Frop = MPIFredholm1(G, nv, saveGt=saveGt, mesh=mesh,
                            dtype=dtype, compute_dtype=compute_dtype)
        if conj:
            Frop = Frop.conj()
    # the transforms make the nfmax bins the product keeps, no more
    Fop = aslinearoperator(_LocalFFT(
        (nt, nr, nv), axis=0, real=True, ifftshift_before=twosided,
        dtype=rdtype, planes=planar, nfkeep=nfmax))
    F1op = aslinearoperator(_LocalFFT(
        (nt, ns, nv), axis=0, real=True, dtype=rdtype, planes=planar,
        nfkeep=nfmax))
    if not prescaled:
        # on the spectrum, not on the kernel: no second kernel is made
        Frop = Frop * rdtype.type(dr * dt * np.sqrt(nt))
    MDCop = _MDCChain(F1op.H * Frop, Fop)
    MDCop.dtype = rdtype
    return MDCop


class _MDCChain(_ProductLinearOperator):
    """``F1ᴴ · (a · Fredholm1) · F``, ``MPIMDC``'s operator: the product
    chain as it was (``matvec`` / ``rmatvec`` unchanged), which knows its
    three factors and so offers what ONE read of the kernel gives CGLS
    (:meth:`fresh_normal_matvec`; ``solvers/basic.py``'s fresh-residual
    body). No other chain is one: ``_ProductLinearOperator`` and
    ``_ScaledLinearOperator`` keep their generic behaviour everywhere
    else. The factors are read from ``args`` at each call, so an
    operator that travels into ``jit`` as a pytree reads its traced
    kernel, never the one it was built with."""

    def _factors(self):
        """``(F1, Fredholm1, a, F)``: ``a`` is ``None`` where the chain
        was built ``prescaled``; the core is whatever stands between the
        transforms (a ``conj`` chain's is a wrapper)."""
        inner, F = self.args
        F1H, core = inner.args
        a = None
        if isinstance(core, _ScaledLinearOperator):
            core, a = core.args
        return F1H.A, core, a, F

    @property
    def has_fresh_normal(self) -> bool:
        """The core is the product on a kernel held as its plane pair
        itself (:class:`MPIFredholm1`, either engine; not a ``conj``
        wrapper, not a real kernel): only then does
        :meth:`fresh_normal_matvec` exist for the chain."""
        core = self._factors()[1]
        return isinstance(core, MPIFredholm1) and core._planes

    def normal_select(self, x):
        """``(form, why, tile)`` of :meth:`fresh_normal_matvec` for the
        vector ``x``: :meth:`MPIFredholm1.normal_form`'s answer (the
        vector has columns where it is no one ``DistributedArray``), but
        ``pair`` with the ``why`` ``nyquist`` where the kept bins hold
        the Nyquist bin of an even ``nt`` (``twosided=False``, the whole
        half spectrum): ``F1ᴴ`` drops that bin's imaginary part too, so
        ``F1 F1ᴴ`` is not the kernel's mask."""
        F1, core = self._factors()[:2]
        form, why, tile = core.normal_form(
            not isinstance(x, DistributedArray) or x.ndim != 1)
        fft = F1.Op
        if form == "one_sweep" and fft.nfft % 2 == 0 \
                and fft.nfkeep == fft.nfft // 2 + 1:
            return "pair", "nyquist", tile
        return form, why, tile

    def prefers_fused_normal(self, x) -> bool:
        """``cgls(normal=None)``'s question: yes exactly where the chain's
        product would be the compiled one-sweep kernel for ``x`` — a
        TPU, the ``complex`` engine, one vector with no columns, a row
        tile and a column count at which the chip has shown one sweep of
        the planes faster than two, no Nyquist bin kept
        (:meth:`normal_select`)."""
        return self.has_fresh_normal and self.normal_select(x)[0] \
            == "one_sweep"

    def fresh_normal_matvec(self, c: DistributedArray, s: DistributedArray):
        """``(q, adjoint)`` with ``q = Op c`` and ``adjoint(t) = Opᴴ (s −
        t q)`` — the normal residual's adjoint after a step ``t`` along
        ``c``. Where :meth:`normal_select` says ``one_sweep``, from ONE
        read of the kernel: ``F c`` and ``F1 s``, then
        :meth:`MPIFredholm1.normal_planes` gives ``Q = G F c``, ``Z1 =
        Gᴴ M Q`` and ``Z2 = Gᴴ F1 s``; ``q = F1ᴴ (a Q)``, and since ``F1
        F1ᴴ = M`` on the kept bins (bin 0's imaginary part is the one
        that ``F1ᴴ`` drops), ``Opᴴ (s − t q) = Fᴴ (a (Z2 − t a Z1))``: one
        adjoint transform, of the spectrum combined once the step is
        known. Each transform is the chain's own (``pmt.local.FFT``), the
        product under ``pmt.MPIFredholm1.normal_matvec``. Where it says
        ``pair``: the chain's own ``matvec`` and ``rmatvec``, a read of
        the kernel each. ``mdc.normal_select`` (``form``, ``cols`` = the
        forward columns ``2 nv``, ``tile``, for ``pair`` a one-word
        ``why``: ``interpret``, ``planar``, ``columns``, ``tile``,
        ``cols``, ``nyquist``) says which under ``PYLOPS_MPI_TPU_TRACE``,
        one a traced apply."""
        F1, core, a, F = self._factors()
        form, why, tile = self.normal_select(c)
        _trace.event("mdc.normal_select", cat="schedule", form=form,
                     cols=2 * core.nz, tile=tile,
                     **({"why": why} if why else {}))
        if form == "pair":
            q = self.matvec(c)
            return q, lambda t: self.rmatvec(s - q * t)
        with _trace.op_span(self, "fresh_normal_matvec"):
            Q, Z1, Z2 = core.normal_planes(F.matvec(c), F1.matvec(s))
            q = F1.rmatvec(Q if a is None else Q * a)

        def adjoint(t):
            with _trace.op_span(self, "fresh_normal_matvec"):
                ta = t if a is None else t * a
                z = Z2 - Z1 * ta
                return F.rmatvec(z if a is None else z * a)
        return q, adjoint


register_operator_arrays(_MDCChain, "args")
