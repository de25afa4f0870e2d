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
from pylops_mpi_tpu.ops import matrixmult as mm
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo

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


# ------------------------------- ring or bulk: `overlap=auto` is a rule
# every row of the probe's table (PERF.md section 6, PR 28; TPU v5e,
# A 65,536^2 f32 on 2 x 2 unless said): (what else the row varied,
# columns a hop, forward ring / bulk ms, adjoint ring / bulk ms)
PROBE = [
    ("", 8, (11.831, 6.177), (11.763, 5.987)),
    ("", 32, (12.644, 6.717), (12.112, 6.582)),
    ("bf16_tiles", 32, (6.512, 3.793), (6.320, 3.513)),
    ("tiles_1.07GB", 32, (3.439, 1.972), (3.195, 1.880)),
    ("grid_1x4", 16, (23.842, 6.847), (23.548, 10.100)),
    ("", 64, (13.538, 10.904), (13.231, 10.902)),
    ("", 128, (20.084, 21.342), (21.021, 18.872)),
    ("", 256, (43.312, 39.735), (37.926, 38.152)),
    ("", 512, (82.880, 80.743), (74.980, 76.174)),
    ("bf16_tiles", 512, (43.196, 42.846), (41.145, 42.616)),
    ("tiles_1.07GB", 512, (22.451, 21.742), (20.264, 20.746)),
    ("grid_1x4", 512, (161.077, 160.640), (142.545, 144.676)),
    ("", 1024, (165.075, 161.902), (151.123, 152.731)),
    ("", 2048, (329.977, 323.638), (301.980, 305.333)),
]
# the one row in which the stationary-A forward's ring read faster (the
# rule's docstring says why it was not chased)
NOT_CHASED = {("matvec", 128, "")}
MEASURED_ROWS = [
    pytest.param(kernel, cols, ms[0] < ms[1],
                 id=f"{kernel}-{cols}" + (f"-{what}" if what else ""))
    for what, cols, fwd, adj in PROBE
    for kernel, ms in (("matvec", fwd), ("rmatvec", adj))
    if (kernel, cols, what) not in NOT_CHASED]


@pytest.mark.parametrize("kernel,cols,ring_was_faster", MEASURED_ROWS)
def test_the_rule_follows_every_measured_row(kernel, cols, ring_was_faster):
    """The adjoint rings where its ring was faster. The stationary-A
    forward's ring was faster in none of these rows: it has no rule
    because it has no ring."""
    if kernel == "rmatvec":
        assert mm._ring_pays(cols) is ring_was_faster
    else:
        assert not ring_was_faster
        assert not hasattr(mm._MPISummaMatrixMult, "_kernel_fwd_stat_a_ring")


def test_the_one_row_the_forwards_ring_won():
    """Forward at 128 columns a hop: the ring read 5.9 % faster, its
    neighbours at 64 and 256 and the adjoint at 128 the other way."""
    rows = {cols: (f, a) for what, cols, f, a in PROBE if not what}
    assert rows[128][0][0] < rows[128][0][1]
    assert all(rows[c][0][0] > rows[c][0][1] for c in (64, 256))
    assert rows[128][1][0] > rows[128][1][1] and not mm._ring_pays(128)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The backend's answer patched to ``tpu``: ``overlap=auto`` then
    resolves as on the chip (``deps.overlap_enabled`` asks nothing else)."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_OVERLAP", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def products(N=64, M=8, grid=(2, 2), cols=None, **kw):
    """A small operator and jitted ``matvec`` / ``rmatvec`` with their
    inputs: ``{"matvec": (fn, Op, x), "rmatvec": (fn, Op, y)}``."""
    A, Y = seeded(19, N, N, M, np.float32, cols=cols or 1)
    mesh = mesh_of(grid)
    Op = summa(A, M, grid, mesh, **kw)
    y = pmt.DistributedArray.to_dist(
        Y.ravel() if cols is None else Y.reshape(N * M, cols), mesh=mesh)
    return Op, {which: (jax.jit(lambda op, v, w=which: getattr(op, w)(v).array),
                        Op, y) for which in ("matvec", "rmatvec")}


def permutes_and_dots(fn, *args):
    text = hlo.compiled_hlo(fn, *args)
    return (len(hlo._op_results(text, "collective-permute")),
            len(hlo._op_results(text, "dot")))


def bulk_counts(which, **kw):
    """``(permutes, dots)`` of the bulk product on the same shapes: one
    local GEMM, and whatever hops the partitioner's own re-layout of
    the 1-D vectors costs (not part of any ring)."""
    _, fns = products(overlap="off", **kw)
    n_perm, n_dots = permutes_and_dots(*fns[which])
    assert n_dots == 1
    return n_perm, n_dots


def assert_rings(fn, op, v, which, **kw):
    """``pc - 1`` hops beyond the bulk product's, ``pc`` local GEMMs."""
    pc = op.grid[1]
    hlo.assert_ring_schedule(fn, op, v, dots=pc, check_chain=False,
                             steps=bulk_counts(which, **kw)[0] + pc - 1)


def test_left_open_on_a_tpu_the_products_lower_as_the_rule_says(as_on_a_tpu):
    """32 columns a hop, as the benchmark's cell: the rule says bulk, so
    a default-constructed operator lowers each product to one local GEMM
    and no hop — the very program ``overlap="off"`` gives."""
    Op, fns = products(N=256, M=64)
    assert Op.overlap == "auto" and Op.schedule == "stat_a"
    assert not mm._ring_pays(64 // Op.grid[1])
    _, off_fns = products(N=256, M=64, overlap="off")
    for which, (fn, op, v) in fns.items():
        text = hlo.compiled_hlo(fn, op, v)
        assert len(hlo._op_results(text, "dot")) == 1
        assert hlo.strip_provenance(text) == hlo.strip_provenance(
            hlo.compiled_hlo(*off_fns[which]))


def test_left_open_on_a_tpu_the_solver_loop_is_the_bulk_program(as_on_a_tpu):
    auto = lowered_cgls(256, M=64)
    assert auto == lowered_cgls(256, M=64, overlap="off")
    assert "collective_permute" not in auto
    assert "collective_permute" in lowered_cgls(256, M=64, overlap="on")


def test_left_open_on_a_tpu_a_wide_adjoint_rings(as_on_a_tpu):
    """256 columns a hop: the rule says ring for the adjoint; the
    stationary-A forward has the one kernel."""
    Op, fns = products(N=2048, M=512)
    assert Op.overlap == "auto" and Op.schedule == "stat_a"
    assert permutes_and_dots(*fns["matvec"]) == \
        bulk_counts("matvec", N=2048, M=512)
    assert_rings(*fns["rmatvec"], "rmatvec", N=2048, M=512)


def test_a_block_input_decides_by_its_own_widened_width(as_on_a_tpu):
    """Eight columns fold M = 64 into 512: 256 columns a hop where the
    plain input has 32, so the same operator's adjoint now rings."""
    _, plain = products(N=256, M=64)
    _, block = products(N=256, M=64, cols=8)
    for which in ("matvec", "rmatvec"):
        assert permutes_and_dots(*plain[which]) == \
            bulk_counts(which, N=256, M=64)
    assert permutes_and_dots(*block["matvec"]) == \
        bulk_counts("matvec", N=256, M=64, cols=8)
    assert_rings(*block["rmatvec"], "rmatvec", N=256, M=64, cols=8)


class SimplePlan(dict):
    """What ``_consult_plan`` hands back, as far as the constructor
    reads it: ``get`` and a ``provenance``."""
    provenance = "tuned"


@pytest.mark.parametrize("how", ["kwarg_true", "kwarg_on", "env", "plan"])
def test_a_word_still_rings_at_the_cells_columns_a_hop(how, monkeypatch,
                                                       as_on_a_tpu):
    """``overlap=True`` / ``"on"``, the env pin and a tuner plan's
    ``on`` choose the ring at 32 columns a hop, whatever the rule says
    — where a ring exists: the adjoint. The stationary-A forward keeps
    the bulk product's counts."""
    kw = {}
    if how == "kwarg_true":
        kw["overlap"] = True
    elif how == "kwarg_on":
        kw["overlap"] = "on"
    elif how == "env":
        monkeypatch.setenv("PYLOPS_MPI_TPU_OVERLAP", "on")
    else:
        monkeypatch.setattr(
            mm._MPISummaMatrixMult, "_consult_plan",
            lambda self, *a: SimplePlan(schedule="stat_a", overlap="on"))
    Op, fns = products(N=256, M=64, **kw)
    assert Op.overlap is True and Op.schedule == "stat_a"
    assert Op._overlap_source == how.split("_")[0]
    assert permutes_and_dots(*fns["matvec"]) == \
        bulk_counts("matvec", N=256, M=64)
    assert_rings(*fns["rmatvec"], "rmatvec", N=256, M=64)


@pytest.mark.parametrize("grid,dtype", [
    ((2, 2), np.float32), ((2, 2), np.complex64),
    ((1, 4), np.float32), ((1, 4), np.complex64)])
def test_a_word_rings_the_adjoint_only(grid, dtype, as_on_a_tpu):
    """Under ``overlap="on"`` the stationary-A forward compiles to the
    very program ``overlap="off"`` gives — it has one kernel — while
    the adjoint's program gains its ``pc - 1`` hops; both products
    still meet the dense ones."""
    N, M = 64, 8
    A, Y = seeded(23, N, N, M, dtype)
    mesh = mesh_of(grid)
    v = pmt.DistributedArray.to_dist(Y.ravel(), mesh=mesh)
    text = {}
    for word in ("on", "off"):
        Op = summa(A, M, grid, mesh, schedule="stat_a", overlap=word)
        for which in ("matvec", "rmatvec"):
            fn = jax.jit(lambda op, x, w=which: getattr(op, w)(x).array)
            text[word, which] = hlo.strip_provenance(
                hlo.compiled_hlo(fn, Op, v))
            want = (A if which == "matvec" else A.conj().T) @ Y[..., 0]
            assert rel(np.asarray(fn(Op, v)).reshape(N, M), want) < 1e-5
    assert text["on", "matvec"] == text["off", "matvec"]
    hops = {w: len(hlo._op_results(text[w, "rmatvec"], "collective-permute"))
            for w in ("on", "off")}
    assert hops["on"] == hops["off"] + grid[1] - 1


@pytest.mark.parametrize("word,source,overlap", [
    (None, "rule", False), ("auto", "rule", False),
    (False, "kwarg", False), ("off", "kwarg", False)])
def test_off_a_tpu_nothing_rings_unless_told(word, source, overlap,
                                             monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_OVERLAP", raising=False)
    Op, fns = products(N=256, M=64, overlap=word)
    assert Op.overlap is overlap and Op._overlap_source == source
    for which in ("matvec", "rmatvec"):
        assert permutes_and_dots(*fns[which]) == bulk_counts(which, N=256, M=64)


@pytest.mark.parametrize("cols,width", [(None, 8), (4, 32)])
def test_each_traced_apply_leaves_its_selection_event(cols, width,
                                                      as_on_a_tpu,
                                                      monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    from pylops_mpi_tpu.diagnostics import trace
    Op, fns = products(M=8, cols=cols)
    trace.clear_events()
    for which in ("matvec", "rmatvec"):
        fn, op, v = fns[which]
        fn.lower(op, v)
    got = [e["args"] for e in trace.get_events()
           if e["name"] == "summa.ring_select"]
    for e in got:
        e.pop("jax_tracing", None)
    tile = Op.Ap.nbytes // 4
    # one event, the adjoint's: the forward has nothing to select
    assert got == [dict(kernel="rmatvec", cols_per_hop=width // 2,
                        tile_bytes=tile, ring=0, source="rule")]
    assert all(e["cat"] == "schedule" for e in trace.get_events()
               if e["name"] == "summa.ring_select")
