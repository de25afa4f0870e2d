"""Deployment builder ``blockdiag``: CGLS on ``MPIBlockDiag`` of dense
``MatrixMult`` blocks (upstream ``examples/plot_cgls.py``).

Generator and plain reference are the benchmark's own copies of
``chip_smoke.make_blocks`` / ``chip_smoke.ref_cgls`` (sound, see
PERF.md): blocks ``N(0,1)/sqrt(n) + 4 I`` from one stream per block,
without the bf16-grid rounding (no cell here stores bf16), written
straight into one host array by a thread pool. The reference imports
nothing from ``pylops_mpi_tpu.solvers`` or ``.ops``: textbook CGLS,
two ``einsum``s under ``highest`` and five vector updates an
iteration, on the operator's own stacked array (a second copy of a
deployment-sized operator does not fit the chip), which is first held
to the generated blocks: float32, and bit for bit on a seeded sample.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np


def make_blocks(nblk: int, n: int, seed: int) -> np.ndarray:
    """``(nblk, n, n)`` float32 on the host, block ``i`` from stream
    ``i`` of ``SeedSequence(seed)``; diagonally dominant, so thirty
    CGLS iterations converge to float32 accuracy."""
    out = np.empty((nblk, n, n), dtype=np.float32)
    streams = np.random.SeedSequence(seed).spawn(nblk)
    scale = np.float32(1.0 / np.sqrt(n))
    diag = np.arange(n)

    def one(i):
        b = out[i]
        np.random.default_rng(streams[i]).standard_normal(
            (n, n), dtype=np.float32, out=b)
        b *= scale
        b[diag, diag] += np.float32(4.0)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(one, range(nblk)))
    return out


def ref_products(A):
    """Plain forward/adjoint products on stacked blocks
    ``A (nblk, m, n)`` for columns ``(nblk, m|n, k)``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def mv(X):
        return jnp.einsum("bmn,bnk->bmk", A, X, precision=hi,
                          preferred_element_type=jnp.float32)

    def rmv(R):
        return jnp.einsum("bmn,bmk->bnk", A, R, precision=hi,
                          preferred_element_type=jnp.float32)

    return mv, rmv


def ref_cgls(A, Y, niter):
    """Textbook CGLS on the stacked blocks, one recurrence per column."""
    import jax.numpy as jnp
    from chipbench import reference
    mv, rmv = ref_products(A)
    return reference.cgls(mv, rmv, lambda U: jnp.sum(U * U, axis=(0, 1)),
                          Y, niter)


def held_as_generated(A, blocks: np.ndarray, seed: int) -> None:
    """The reference, the right-hand sides and ``costs`` all use the
    operator's own stored array ``A``, so it has to BE what the seed
    generated: same shape, float32 (the guarantee admits no lower
    precision), and — on two seeded rows of every block, the diagonal
    entry among them — the same bits as the host blocks. A few
    megabytes pulled, not a second copy."""
    import jax
    import jax.numpy as jnp
    if A.shape != blocks.shape or A.dtype != np.float32:
        raise RuntimeError(
            f"operator holds {A.dtype}{A.shape}, generated "
            f"{blocks.dtype}{blocks.shape}: the reference cannot use it")
    nblk, n, _ = blocks.shape
    bi = np.repeat(np.arange(nblk), 2)
    ri = np.random.default_rng([int(seed), 0xB10C]).integers(
        0, n, size=bi.size)
    got = np.asarray(jax.jit(lambda a, b, r: a[b, r])(
        A, jnp.asarray(bi), jnp.asarray(ri)))
    if not np.array_equal(got.view(np.uint32),
                          blocks[bi, ri].view(np.uint32)):
        rows = np.flatnonzero((got != blocks[bi, ri]).any(axis=1))
        raise RuntimeError(
            "the operator's stored blocks are not bit for bit what the "
            f"seed generated (sampled rows differ in blocks "
            f"{sorted(set(bi[rows].tolist()))[:8]})")


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops.local import MatrixMult

    P = int(mesh.devices.size)
    n = int(sizes["n"])
    nblk = int(sizes["blocks_per_chip"]) * P
    N = nblk * n

    t0 = time.perf_counter()
    blocks = make_blocks(nblk, n, seed)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    Op = pmt.MPIBlockDiag(
        [MatrixMult(blocks[i], dtype=np.float32) for i in range(nblk)],
        mesh=mesh)
    A, = jax.tree_util.tree_leaves(Op)
    jax.block_until_ready(A)
    construct_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    held_as_generated(A, blocks, seed)
    del blocks
    verify_s = time.perf_counter() - t0

    col = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    to_cols = lambda V: jax.device_put(
        np.ascontiguousarray(V).reshape(nblk, n, -1), col)
    fwd = jax.jit(lambda a, X: ref_products(a)[0](X))
    ref = jax.jit(ref_cgls, static_argnums=2)

    def rhs(k: int, seed_: int):
        """``k`` right-hand sides made from true models:
        ``(Y, Xtrue)`` on the host, ``Y = A Xtrue`` by the plain
        product."""
        rng = np.random.default_rng([int(seed_), 0x5EED])
        Xt = rng.standard_normal((N, k), dtype=np.float32)
        Y = np.asarray(fwd(A, to_cols(Xt))).reshape(N, k)
        return Y, Xt

    def reference(Y: np.ndarray, niter: int) -> np.ndarray:
        return np.asarray(ref(A, to_cols(Y), niter)).reshape(N, -1)

    from chipbench import costs
    item = int(jnp.dtype(A.dtype).itemsize)
    return SimpleNamespace(
        op=Op, mesh=mesh, nrows=N, ncols=N, rhs=rhs, reference=reference,
        cost=lambda k=1: costs.blockdiag(dict(sizes, itemsize=item), k),
        dtype=str(A.dtype),
        split={"generate_s": gen_s, "construct_s": construct_s,
               "verify_s": verify_s},
        describe=f"{nblk} blocks of {n}x{n} {A.dtype} "
                 f"({A.nbytes // P} bytes a chip)")
