"""Deployment builder ``summa``: CGLS on ``MPIMatrixMult(kind="summa")``
over a 2-D process grid (upstream ``examples/plot_summamatrixmult.py``).

``A`` is born on the device: one jitted ``jax.random`` program from the
seed whose output sharding is the operator's own ``P("r", "c")`` over
the grid, family ``N(0,1)/sqrt(n) + 4 I`` — no gigabyte crosses the
host. The plain reference is textbook CGLS with ``jnp.matmul`` under
``highest`` on the same sharded array, partitioned by XLA alone: no
``shard_map``, nothing imported from ``pylops_mpi_tpu.ops`` or
``.solvers``.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np


def ref_cgls(A, Y, niter):
    """Textbook CGLS for ``A X = Y``. The program's CGLS treats the
    flattened ``(N, M)`` data as ONE vector, so the reference runs one
    recurrence over all M columns together."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    hi = jax.lax.Precision.HIGHEST
    return reference.cgls(lambda X: jnp.matmul(A, X, precision=hi),
                          lambda R: jnp.matmul(A.T, R, precision=hi),
                          lambda U: jnp.sum(U * U), Y, niter)


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import pylops_mpi_tpu as pmt

    N, K, M = int(sizes["N"]), int(sizes["K"]), int(sizes["M"])
    grid = tuple(int(g) for g in sizes["grid"])
    if int(mesh.devices.size) != grid[0] * grid[1]:
        raise RuntimeError(f"grid {grid} needs {grid[0] * grid[1]} devices,"
                           f" the mesh has {mesh.devices.size}")
    mesh2 = Mesh(mesh.devices.reshape(grid), ("r", "c"))
    tiled = NamedSharding(mesh2, P("r", "c"))
    rows = NamedSharding(mesh2, P("r", None))

    def gen(key):
        a = jax.random.normal(key, (N, K), jnp.float32)
        a = a * jnp.float32(1.0 / np.sqrt(K))
        # no scatter (.at) on a partitioned operand: the diagonal is
        # added as a comparison mask
        eye = (jnp.arange(N)[:, None] == jnp.arange(K)[None, :])
        return a + jnp.float32(4.0) * eye.astype(jnp.float32)

    t0 = time.perf_counter()
    A = jax.jit(gen, out_shardings=tiled)(jax.random.key(int(seed)))
    jax.block_until_ready(A)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    Op = pmt.MPIMatrixMult(A, M=M, kind="summa", mesh=mesh,
                           dtype=np.float32, grid=grid)
    jax.block_until_ready(jax.tree_util.tree_leaves(Op))
    construct_s = time.perf_counter() - t0
    log(f"summa: schedule={getattr(Op, 'schedule', '?')} "
        f"overlap={getattr(Op, 'overlap', '?')} grid={grid}")

    hi = jax.lax.Precision.HIGHEST
    fwd = jax.jit(lambda a, X: jnp.matmul(a, X, precision=hi),
                  out_shardings=rows)
    ref = jax.jit(ref_cgls, static_argnums=2)

    def rhs(k: int, seed_: int):
        """``k`` right-hand sides, each a flattened ``(N, M)`` block
        made from a true ``(K, M)`` model by the plain product."""
        rng = np.random.default_rng([int(seed_), 0x5EED])
        Xt = rng.standard_normal((K * M, k), dtype=np.float32)
        Y = np.empty((N * M, k), dtype=np.float32)
        for j in range(k):
            Y[:, j] = np.asarray(
                fwd(A, jnp.asarray(Xt[:, j].reshape(K, M)))).ravel()
        return Y, Xt

    def reference(Y: np.ndarray, niter: int) -> np.ndarray:
        out = np.empty((K * M, Y.shape[1]), dtype=np.float32)
        for j in range(Y.shape[1]):
            yj = jax.device_put(Y[:, j].reshape(N, M), rows)
            out[:, j] = np.asarray(ref(A, yj, niter)).ravel()
        return out

    from chipbench import costs
    item = int(jnp.dtype(A.dtype).itemsize)
    return SimpleNamespace(
        op=Op, mesh=mesh, nrows=N * M, ncols=K * M, rhs=rhs,
        reference=reference,
        cost=lambda k=1: costs.summa(dict(sizes, itemsize=item), k),
        dtype=str(A.dtype),
        split={"generate_s": gen_s, "construct_s": construct_s},
        describe=f"A {N}x{K} {A.dtype} on a {grid[0]}x{grid[1]} grid "
                 f"({A.nbytes // (grid[0] * grid[1])} bytes a chip), "
                 f"M={M} columns, schedule "
                 f"{getattr(Op, 'schedule', '?')}, overlap "
                 f"{getattr(Op, 'overlap', '?')}")
