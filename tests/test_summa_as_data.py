"""The SUMMA operator's matrix travels into the solvers as DATA.

``_MPISummaMatrixMult`` holds one copy of its matrix — the padded,
``P("r", "c")``-tiled ``Ap`` — and that copy is the registered pytree
child its kernels read. So the operator is a jit argument of every
fused solver, no lowered program grows with ``N*K``, a matrix that
arrives tiled and divisible is taken as it is, and the gradient with
respect to the matrix lands on the tiles.

The reference here is plain: textbook CGLS in ``jax.numpy`` float32
(complex64) under ``highest`` on the dense seeded matrix — no
``shard_map``, nothing from ``pylops_mpi_tpu.ops`` / ``.solvers``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.autodiff import cgls_solve
from pylops_mpi_tpu.linearoperator import operator_is_jit_arg
from pylops_mpi_tpu.solvers import basic

NITER = 30
HI = jax.lax.Precision.HIGHEST
# ``pmt.cgls`` freezes its step once the recurrence norm reaches its
# machine-precision floor (``solvers/basic._mp_floor``), about ten
# iterations in on these matrices and 0.5e-5 to 2e-5 from the true
# model by seed, while the textbook recurrence runs on to 1e-7. So the
# operator is held to the reference tightly BEFORE the floor (eight
# iterations: 1e-6, seen 1e-7) and to the benchmark cell's guarantee
# (1e-4) at the cell's thirty.
EARLY, TIGHT, LOOSE = 8, 1e-6, 1e-4


# --------------------------------------------------- the plain reference
def ref_cgls(A, Y, niter=NITER):
    """Textbook CGLS for ``A X = Y``, zero start, no stopping test. The
    squared norm sums over the LEADING axis only: a flattened ``(N*M,)``
    right-hand side comes as ``(N*M, 1)``-shaped ``(N, M, 1)`` data and
    runs ONE recurrence over its M columns (what ``pmt.cgls`` does),
    a K-column block input one recurrence a column."""
    A = jnp.asarray(A)

    def mv(X):
        return jnp.einsum("nk,kmc->nmc", A, X, precision=HI)

    def rmv(R):
        return jnp.einsum("nk,nmc->kmc", A.conj(), R, precision=HI)

    def dot(U):
        return jnp.sum((U * U.conj()).real, axis=(0, 1))

    s = jnp.asarray(Y)
    r = rmv(s)
    c = r
    q = mv(c)
    x = jnp.zeros_like(r)
    kold = dot(r)
    for _ in range(niter):
        a = kold / dot(q)
        x = x + a * c
        s = s - a * q
        r = rmv(s)
        k = dot(r)
        c = r + (k / kold) * c
        q = mv(c)
        kold = k
    return np.asarray(x)


def seeded(seed, N, K, M, dtype, cols=1):
    """``A = N(0,1)/sqrt(K) + 4 I`` and a right-hand side made from a
    true model by the plain product: ``(A, Y)`` with ``Y`` of shape
    ``(N, M, cols)``."""
    rng = np.random.default_rng([seed, N, K, M])
    cplx = np.issubdtype(np.dtype(dtype), np.complexfloating)

    def normal(*shape):
        z = rng.standard_normal(shape)
        if cplx:
            z = z + 1j * rng.standard_normal(shape)
        return z

    A = (normal(N, K) / np.sqrt(K) + 4.0 * np.eye(N, K)).astype(dtype)
    Xt = normal(K, M, cols).astype(dtype)
    Y = np.einsum("nk,kmc->nmc", A.astype(np.complex128 if cplx
                                          else np.float64), Xt)
    return A, Y.astype(dtype)


def mesh_of(grid):
    ndev = grid[0] * grid[1]
    if ndev > len(jax.devices()):
        pytest.skip(f"grid {grid} needs {ndev} devices")
    return pmt.make_mesh(ndev)


def rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def summa(A, M, grid, mesh=None, **kw):
    return pmt.MPIMatrixMult(A, M=M, kind="summa", grid=grid,
                             mesh=mesh if mesh is not None
                             else mesh_of(grid), dtype=A.dtype, **kw)


# grids x (divisible, non-divisible N, K, M) x schedules x overlap
SOLVES = [
    (grid, shape, schedule, overlap, np.float32)
    for grid in [(2, 2), (1, 4), (2, 4)]
    for shape, schedule, overlap in [
        ((64, 64, 8), "stat_a", "on"), ((64, 64, 8), "gather", "off"),
        ((50, 37, 5), "stat_a", "off"), ((50, 37, 5), "gather", "on"),
        ((64, 64, 8), "auto", None)]
] + [
    ((2, 2), (64, 64, 8), "stat_a", "off", np.float32),
    ((2, 2), (64, 64, 8), "gather", "on", np.float32),
    ((2, 2), (50, 37, 5), "stat_a", "on", np.complex64),
    ((2, 4), (64, 64, 8), "gather", "off", np.complex64),
    ((1, 4), (37, 50, 6), "auto", None, np.complex64),
]


@pytest.mark.parametrize("grid,shape,schedule,overlap,dtype", SOLVES)
def test_cgls_on_summa_agrees_with_the_plain_reference(
        grid, shape, schedule, overlap, dtype):
    N, K, M = shape
    A, Y = seeded(11, N, K, M, dtype)
    mesh = mesh_of(grid)
    Op = summa(A, M, grid, mesh, schedule=schedule, overlap=overlap)
    y = pmt.DistributedArray.to_dist(Y.ravel(), mesh=mesh)
    for niter, tol in ((EARLY, TIGHT), (NITER, LOOSE)):
        x = pmt.cgls(Op, y, niter=niter, tol=0.0)[0]
        assert rel(x.asarray(), ref_cgls(A, Y, niter).ravel()) <= tol


@pytest.mark.parametrize("grid,shape,overlap", [
    ((2, 2), (64, 64, 8), "on"), ((2, 4), (50, 37, 5), "off"),
    ((1, 4), (50, 37, 5), "on")])
def test_block_cgls_on_summa_agrees_with_the_plain_reference(
        grid, shape, overlap):
    """A K-column block input folds into the GEMM's columns; every
    column runs its own recurrence."""
    N, K, M = shape
    cols = 3
    A, Y = seeded(12, N, K, M, np.float32, cols=cols)
    mesh = mesh_of(grid)
    Op = summa(A, M, grid, mesh, overlap=overlap)
    y = pmt.DistributedArray.to_dist(Y.reshape(N * M, cols), mesh=mesh)
    for niter, tol in ((EARLY, TIGHT), (NITER, LOOSE)):
        got = pmt.block_cgls(Op, y, niter=niter, tol=0.0)[0].asarray()
        want = ref_cgls(A, Y, niter).reshape(K * M, cols)
        assert max(rel(got[:, j], want[:, j]) for j in range(cols)) <= tol


# --------------------------------------------- one copy, and it is a leaf
@pytest.mark.parametrize("shape,kw", [
    ((64, 64, 8), {}), ((50, 37, 5), {}), ((64, 64, 8), {"saveAt": True}),
    ((50, 37, 5), {"compute_dtype": jnp.bfloat16})])
def test_the_tiles_are_the_leaf_and_the_only_copy(shape, kw):
    N, K, M = shape
    A, _ = seeded(13, N, K, M, np.float32)
    Op = summa(A, M, (2, 2), **kw)
    leaves = jax.tree_util.tree_leaves(Op)
    assert any(l is Op.Ap for l in leaves)
    assert operator_is_jit_arg(Op)
    big = [name for name, v in vars(Op).items()
           if isinstance(v, (jax.Array, np.ndarray)) and v.size >= N * K]
    assert big == ["Ap"]
    # the logical matrix is a view over the tiles
    assert Op.A.shape == (N, K)
    want = A if "compute_dtype" not in kw else \
        np.asarray(jnp.asarray(A).astype(jnp.bfloat16))
    assert np.array_equal(np.asarray(Op.A), want)
    pad = np.asarray(Op.Ap, dtype=np.float32).copy()
    pad[:N, :K] = 0
    assert not pad.any()


def tiled_on(mesh, grid, A):
    mesh2 = Mesh(mesh.devices.reshape(grid), ("r", "c"))
    return jax.device_put(A, NamedSharding(mesh2, P("r", "c")))


def buffers(x):
    return sorted(s.data.unsafe_buffer_pointer()
                  for s in x.addressable_shards)


@pytest.mark.parametrize("grid", [(2, 2), (2, 4)])
def test_a_tiled_divisible_array_is_taken_as_it_is(grid, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    from pylops_mpi_tpu.diagnostics import trace
    N, K, M = 128, 128, 8
    mesh = mesh_of(grid)
    A, _ = seeded(14, N, K, M, np.float32)
    Ad = tiled_on(mesh, grid, A)
    tile = Ad.nbytes // (grid[0] * grid[1])

    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    trace.clear_events()
    before = live()
    Op = summa(Ad, M, grid, mesh)
    jax.block_until_ready(Op.Ap)
    assert live() - before < tile
    assert buffers(Op.Ap) == buffers(Ad)
    sel, = [e for e in trace.get_events()
            if e["name"] == "summa.schedule_select"]
    assert sel["args"]["copied"] == 0
    assert sel["args"]["tile_bytes"] == tile

    # a host matrix, a ragged one and a cast are copies, and say so
    trace.clear_events()
    summa(A, M, grid, mesh)
    summa(tiled_on(mesh, (1, grid[0] * grid[1]), A), M, grid, mesh)
    summa(Ad, M, grid, mesh, compute_dtype=jnp.bfloat16)
    assert [e["args"]["copied"] for e in trace.get_events()
            if e["name"] == "summa.schedule_select"] == [1, 1, 1]


# ----------------------------------- the program holds none of the matrix
def lowered_cgls(N, grid=(2, 2), M=8, **kw):
    """StableHLO text of the fused program ``pmt.cgls`` runs for a
    SUMMA operator of ``N x N`` (the jit behind its ``_FUSED_CACHE``
    entry, lowered with the operator it was built for)."""
    A, Y = seeded(15, N, N, M, np.float32)
    mesh = mesh_of(grid)
    Op = summa(A, M, grid, mesh, **kw)
    y = pmt.DistributedArray.to_dist(Y.ravel(), mesh=mesh)
    x0 = pmt.DistributedArray.to_dist(np.zeros(N * M, np.float32),
                                      mesh=mesh)
    pmt.cgls(Op, y, x0, niter=NITER, tol=0.0)
    (fn, _, _), = [v for k, v in basic._FUSED_CACHE.items()
                   if k[0] == id(Op)]
    bound = fn.__kwdefaults__
    return bound["_jfn"].lower(bound["_op"], y, x0, 0.0, 0.0).as_text()


def literal_bytes(text):
    """Bytes of the largest dense literal in a StableHLO text (hex
    blobs count half their digits, element lists four bytes a comma)."""
    sizes = [0]
    for blob in re.findall(r'dense<"0x([0-9A-Fa-f]+)">', text):
        sizes.append(len(blob) // 2)
    for elems in re.findall(r"dense<\[([^>]*)\]>", text):
        sizes.append(4 * (elems.count(",") + 1))
    return max(sizes)


@pytest.mark.parametrize("overlap", ["on", "off"])
def test_the_solver_program_does_not_grow_with_the_matrix(overlap):
    small = lowered_cgls(64, overlap=overlap)
    large = lowered_cgls(256, overlap=overlap)
    assert literal_bytes(small) <= 256 and literal_bytes(large) <= 256
    # sixteen times the matrix, the same text but for its shapes' digits
    assert abs(len(large) - len(small)) <= 600


def dot_scopes(jaxpr, outer=""):
    """The name stack of every ``dot_general`` in ``jaxpr`` and the
    jaxprs nested in it (the ``shard_map`` body), outermost first."""
    found = []
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "dot_general":
            found.append(stack)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                found += dot_scopes(inner, stack)
    return found


def test_every_local_gemm_has_its_scope():
    """``pmt.summa.gemm`` names each local GEMM of the six kernels, so
    a device trace separates GEMM from hops under ``ring_pass``."""
    N, K, M = 64, 64, 8
    A, Y = seeded(16, N, K, M, np.float32)
    grid = (2, 2)
    mesh = mesh_of(grid)
    x = pmt.DistributedArray.to_dist(Y.ravel(), mesh=mesh)
    seen = 0
    for schedule in ("gather", "stat_a"):
        for overlap in ("on", "off"):
            Op = summa(A, M, grid, mesh, schedule=schedule, overlap=overlap)
            for which in ("matvec", "rmatvec"):
                scopes = dot_scopes(jax.make_jaxpr(
                    lambda op, v: getattr(op, which)(v).array)(Op, x).jaxpr)
                assert scopes and all(
                    s.endswith("pmt.summa.gemm") and
                    f"pmt._MPISummaMatrixMult.{which}" in s for s in scopes)
                ring = [s for s in scopes if "pmt.collective.ring_pass" in s]
                seen += len(ring)
    assert seen        # the ring kernels' GEMMs sit under ring_pass


# ------------------------------------------- the gradient lands on the tiles
@pytest.mark.parametrize("shape", [(32, 32, 4), (26, 19, 3)])
def test_gradient_with_respect_to_the_matrix(shape):
    """``jax.grad`` through ``cgls_solve`` with respect to the operator
    — its one leaf, the tiles — against the dense reference: the
    gradient of ``<w, argmin |A X - Y|>`` by ``jax.grad`` through a
    dense float64 normal-equations solve. ``g.A``, the same property
    over the cotangent tiles, is the gradient in A's shape; the pad
    carries none."""
    N, K, M = shape
    A, Y = seeded(17, N, K, M, np.float64)
    grid = (2, 2)
    mesh = mesh_of(grid)
    Op = summa(A, M, grid, mesh)
    y = pmt.DistributedArray.to_dist(Y.ravel(), mesh=mesh)
    w = jnp.asarray(np.random.default_rng(18).standard_normal((K, M)))

    def loss(op):
        x = cgls_solve(op, y, niter=200, tol=0.0)
        return jnp.vdot(w.ravel(), x.array).real

    def dense_loss(a):
        x = jnp.linalg.solve(a.T @ a, a.T @ jnp.asarray(Y[:, :, 0]))
        return jnp.vdot(w, x).real

    g = jax.grad(loss)(Op)
    assert g.Ap.shape == Op.Ap.shape and g.A.shape == (N, K)
    want = np.asarray(jax.grad(dense_loss)(jnp.asarray(A)))
    assert rel(np.asarray(g.A), want) <= 1e-8
    pad = np.asarray(g.Ap).copy()
    pad[:N, :K] = 0
    assert not pad.any()
