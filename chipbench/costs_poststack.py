"""Operations and bytes one CGLS iteration of the ``poststack``
deployment needs on one chip, from the configuration's shapes, in the
form ``costs.least_seconds`` takes (``{"flops", "bytes"}``). ``V`` is the
chip's share of the cube in elements, float32.

An iteration is one forward and one adjoint apply of the stacked system
``[0.5 W D; sqrt(epsR) Lap]``. **Bytes**: the six volume-sized streams
the algebra cannot avoid — read the direction ``c``, write ``q1`` and
``q2``; read the residuals ``s1`` and ``s2``, write the adjoint's sum.
**Flops**: a multiply-add a tap and element for the convolution and for
its adjoint; a subtraction and a scaling for the derivative, both ways;
three operations an axis, two additions and the scaling for the
Laplacian, both ways; the modelling's one half, both ways. The solver's
own vector updates and reductions are left out, as in ``costs.py``.
"""

from __future__ import annotations


def _volume(sizes: dict) -> int:
    return int(sizes["ny"]) * int(sizes["nx"]) * int(sizes["nt0"])


def convolution(sizes: dict, ntaps: int) -> dict:
    """The convolution and its adjoint alone: each reads a volume and
    writes one, ``2 * ntaps`` flops an element."""
    V = _volume(sizes)
    return {"flops": float(2 * 2 * ntaps * V), "bytes": float(4 * V * 4)}


def iteration(sizes: dict, ntaps: int) -> dict:
    V = _volume(sizes)
    stencils = 2 * 2 + 2 * (3 * 3 + 2 + 1) + 2      # D, Lap, the half
    return {"flops": convolution(sizes, ntaps)["flops"]
            + float(stencils * V),
            "bytes": float(6 * V * 4)}
