"""Operations and bytes one CGLS iteration of the ``mdd`` deployment
needs on one chip, from the configuration's shapes, in the form
``costs.least_seconds`` takes (``{"flops", "bytes"}``; flops are REAL
operations, float32 vectors and a complex64 kernel).

An iteration is one forward and one adjoint apply of ``MDC = F1^H I1^H
(a G) I F``: a real FFT of ``nt`` samples over ``nr * nv`` traces and
one over ``ns * nv`` each way, and the complex batched product with the
chip's ``nfmax`` frequencies of ``G (ns x nr)`` each way.

**Bytes** — what the algebra cannot avoid, whatever implements it: the
kernel ONCE an iteration at its stored 8 bytes an element (a one-sweep
normal product can make both products from one read, so a later one
cannot read over 100 %), plus the four time-domain vector streams (read
the direction, write ``q``; read the residual, write the gradient).
**Flops**: 8 a complex multiply-add, both products; ``2.5 n log2 n`` a
real FFT of ``n`` samples, four of them. The solver's own vector
updates and reductions are left out, as in ``costs.py``.
"""

from __future__ import annotations

import math


def _n(sizes: dict):
    return tuple(int(sizes[k]) for k in ("nfmax", "ns", "nr", "nt", "nv"))


def fredholm(sizes: dict) -> dict:
    """Both complex products of an iteration alone: the kernel read
    ONCE, their four spectra (read ``nr * nv``, write ``ns * nv``, and
    back) at 8 bytes an element."""
    nf, ns, nr, _, nv = _n(sizes)
    return {"flops": float(2 * 8 * nf * ns * nr * nv),
            "bytes": float(8 * nf * ns * nr + 2 * 8 * nf * (ns + nr) * nv)}


def iteration(sizes: dict) -> dict:
    nf, ns, nr, nt, nv = _n(sizes)
    ffts = 2 * 2.5 * nt * math.log2(nt) * (ns + nr) * nv
    return {"flops": fredholm(sizes)["flops"] + float(ffts),
            "bytes": float(8 * nf * ns * nr + 2 * 4 * nt * (ns + nr) * nv)}
