"""Reverse-differentiable fixed-iteration CG/CGLS (scan tape).

The oracle the implicit rules are checked against, and their
baseline: a ``lax.scan`` over exactly ``niter``
iterations is what a user without implicit diff would write —
reverse-differentiable because scan saves the per-iteration carry as
a tape, which is precisely its cost: O(niter · n) activation memory
and a backward pass that replays every iteration, versus the implicit
rule's ONE extra solve. Single-RHS only (the tests reduce block
gradients column-wise against this).

Math mirrors ``basic._make_cg_body`` / ``_make_cgls_body`` (same
``_rdot`` reduction dtype, same ``_mp_floor`` freeze — a tape through
``0/0`` past convergence would poison the gradient with NaNs), minus
the early-exit ``tol`` check: the tape runs the full ``niter``
schedule, which is also what makes it a fair memory/wall baseline.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["unrolled_cg", "unrolled_cgls"]


def unrolled_cg(Op, y, x0=None, *, niter: int = 10, M=None):
    """Fixed-``niter`` (P)CG as a differentiable scan; returns ``x``."""
    from ..solvers.basic import (_rdot, _step_scalar, _precond_apply,
                                 _mp_floor, _vdtype, _zero_like_model)
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    xdt = _vdtype(x0)
    x = x0
    r = y - Op.matvec(x)
    z = _precond_apply(M, r, xdt)
    c = z
    kold = _rdot(r, z)
    floors = _mp_floor(kold)

    def step(carry, _):
        x, r, c, kold = carry
        done = kold <= floors
        q = Op.matvec(c)
        a = kold / _rdot(c, q)
        a = jnp.where(done, jnp.zeros_like(a), a)
        x = x + c * _step_scalar(a, xdt)
        r = r - q * _step_scalar(a, xdt)
        z = _precond_apply(M, r, xdt)
        k = _rdot(r, z)
        k = jnp.where(done, kold, k)
        b = jnp.where(done, jnp.zeros_like(k), k / kold)
        c = z + c * _step_scalar(b, xdt)
        return (x, r, c, k), None

    (x, _, _, _), _ = lax.scan(step, (x, r, c, kold), None,
                               length=niter)
    return x


def unrolled_cgls(Op, y, x0=None, *, niter: int = 10,
                  damp: float = 0.0, M=None):
    """Fixed-``niter`` (P)CGLS (classic two-sweep) as a differentiable
    scan; returns ``x``. ``damp`` quirk matches the fused setup
    (initial gradient uses un-squared ``damp``, steps use ``damp²`` —
    solvers/basic.py module doc)."""
    from ..solvers.basic import (_rdot, _step_scalar, _precond_apply,
                                 _mp_floor, _vdtype, _zero_like_model)
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    damp2 = damp ** 2
    xdt = _vdtype(x0)
    x = x0
    s = y - Op.matvec(x)
    rq = Op.rmatvec(s) - x * damp
    z = _precond_apply(M, rq, xdt)
    c = z
    kold = _rdot(rq, z)
    floors = _mp_floor(kold)

    def step(carry, _):
        x, s, c, kold = carry
        done = kold <= floors
        q = Op.matvec(c)
        den = _rdot(q, q) + damp2 * _rdot(c, c)
        a = kold / den
        a = jnp.where(done, jnp.zeros_like(a), a)
        x = x + c * _step_scalar(a, xdt)
        s = s - q * _step_scalar(a, xdt)
        rq = Op.rmatvec(s) - x * damp2
        z = _precond_apply(M, rq, xdt)
        k = _rdot(rq, z)
        k = jnp.where(done, kold, k)
        b = jnp.where(done, jnp.zeros_like(k), k / kold)
        c = z + c * _step_scalar(b, xdt)
        return (x, s, c, k), None

    (x, _, _, _), _ = lax.scan(step, (x, s, c, kold), None,
                               length=niter)
    return x
