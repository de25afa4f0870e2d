"""Deployment builder ``summa_grid``: the ``summa`` builder's
deployment (generator, operator, plain reference — ``builders/
summa.py``), for an operator too large to be anything but DATA to the
solver. After the build it refuses an operator whose matrix would be
embedded in the solver's program: one ``matvec`` and one ``rmatvec``
are traced (``jax.make_jaxpr``, nothing compiles, no data moves) with
the operator passed as the argument, as the fused solvers pass it, and
no closed-over constant may reach a tile's bytes. Counting the leaves'
bytes would not do: a program can register one array as its leaf and
read another. At 65,536^2 the program such an operator lowers to holds
a 17 GB literal; this builder will not time what it cannot vouch for
(``builders/blockdiag.held_as_generated`` is the precedent).
"""

from __future__ import annotations

import time


def embedded_bytes(op, x, y) -> int:
    """Bytes of the largest constant the traced ``op.matvec(x)`` and
    ``op.rmatvec(y)`` close over when ``op`` itself is an argument."""
    import jax
    closed = jax.make_jaxpr(
        lambda o, u, v: (o.matvec(u), o.rmatvec(v)))(op, x, y)
    return max((int(getattr(c, "nbytes", 0)) for c in closed.consts),
               default=0)


def build(cfg: dict, sizes: dict, seed: int, mesh, log):
    import numpy as np
    import pylops_mpi_tpu as pmt
    from chipbench.builders import summa

    dep = summa.build(cfg, sizes, seed, mesh, log)
    t0 = time.perf_counter()
    grid = [int(g) for g in sizes["grid"]]
    tile = int(sizes["N"]) * int(sizes["K"]) * np.dtype(dep.dtype).itemsize \
        // (grid[0] * grid[1])
    x = pmt.DistributedArray.to_dist(
        np.zeros(dep.ncols, dtype=dep.dtype), mesh=mesh)
    y = pmt.DistributedArray.to_dist(
        np.zeros(dep.nrows, dtype=dep.dtype), mesh=mesh)
    worst = embedded_bytes(dep.op, x, y)
    if worst >= tile:
        raise RuntimeError(
            f"the solver's program would embed {worst} bytes of the "
            f"operator's matrix as a constant (a tile is {tile} bytes): "
            "the kernels read an array that is not a pytree child of "
            f"{type(dep.op).__name__}, so the matrix is code, not data")
    dep.split["embed_check_s"] = time.perf_counter() - t0
    log(f"summa_grid: largest closed-over constant {worst} bytes "
        f"(a tile is {tile})")
    return dep
