"""The plain reference solver: textbook CGLS, independent of
``pylops_mpi_tpu.solvers``. A builder supplies its own plain products
(``mv``, ``rmv``) and the squared norm that defines its recurrences
(``dot``: one number for one recurrence over everything, one number a
column for per-column recurrences); the iteration is the same for all.
Copy of ``chip_smoke.ref_cgls``'s arithmetic: two products and five
vector updates an iteration, zero start, no stopping test.
"""

from __future__ import annotations


def cgls(mv, rmv, dot, Y, niter: int):
    import jax
    import jax.numpy as jnp

    s = Y
    r = rmv(s)
    c = r
    q = mv(c)
    x = jnp.zeros_like(r)

    def body(_, st):
        x, s, c, q, kold = st
        a = kold / dot(q)
        x = x + a * c
        s = s - a * q
        r = rmv(s)
        k = dot(r)
        c = r + (k / kold) * c
        return x, s, c, mv(c), k

    return jax.lax.fori_loop(0, niter, body, (x, s, c, q, dot(r)))[0]
