"""Operations and bytes one CGLS iteration of the ``lsm`` deployment
needs on one chip, from the configuration's shapes, in the form
``costs.least_seconds`` takes (``{"flops", "bytes"}``, float32).

An iteration is one forward and one adjoint apply of the chip's share
of the Kirchhoff demigration: the indexed spray of ``npix`` pixels onto
``pairs`` traces of ``nt`` samples through the per-pair tables ``(i,
tau)`` and the gather back, and the wavelet along time each way.

**Bytes** — what the algebra cannot avoid, whatever implements it: the
``pairs x npix`` table entries ONCE an iteration at their stored 8
bytes (int32 index, float32 fraction; a fused normal product could
serve both applies from one read, as ``costs_mdd.fredholm`` counts its
kernel, so a later one cannot read over 100 %), the image read and
written and the traces written and read. **Flops**: 8 a pair-pixel an
iteration — two weights, two multiplies and two adds each way, counted
as the four multiply-adds — at the float32 peak. Bytes bind: 10.5 ms
against 0.26 on a v5e at the configuration's sizes.

The count is fixed by the configuration's sizes, whatever implements
the apply: a program that DERIVES the pairs' entries in its kernel
from the per-point travel times (``(ns + nr) x npix``, as newer PyLops
does) is read against the same count, and a reading over 100 % is a
``benchmark`` issue's to re-count, not a fault of the program. The
solver's own vector updates and reductions are left out, as in
``costs.py``.
"""

from __future__ import annotations


def _n(sizes: dict):
    ns, nr, nz, nx, nt, nw = (int(sizes[k]) for k in
                              ("ns", "nr", "nz", "nx", "nt", "nwav"))
    return ns * nr, nz * nx, nt, nw


def kirchhoff(sizes: dict) -> dict:
    """Both indexed applies of an iteration alone: the tables read
    ONCE, the image read and written, the traces written and read."""
    pairs, npix, nt, _ = _n(sizes)
    return {"flops": float(8 * pairs * npix),
            "bytes": float(8 * pairs * npix + 2 * 4 * npix
                           + 2 * 4 * pairs * nt)}


def iteration(sizes: dict) -> dict:
    """The whole operator: the wavelet's streams (a read and a write of
    the traces each way) and its ``2 nwav`` flops a sample each way
    beside :func:`kirchhoff`."""
    pairs, npix, nt, nw = _n(sizes)
    k = kirchhoff(sizes)
    return {"flops": k["flops"] + float(2 * 2 * nw * pairs * nt),
            "bytes": k["bytes"] + float(2 * 2 * 4 * pairs * nt)}
