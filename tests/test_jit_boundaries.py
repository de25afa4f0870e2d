"""Jit-boundary tests (round-1 VERDICT weak #8): DistributedArray and
StackedDistributedArray as pytrees through jit, masked solves inside a
single compiled program, and collective-schedule assertions on the
lowered solver loop."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import (DistributedArray, StackedDistributedArray,
                            Partition, MPIBlockDiag, MPIGradient,
                            MPIStackedVStack)
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.solvers.basic import _cg_fused, _cgls_fused


def test_distributedarray_pytree_roundtrip(rng):
    """DistributedArray flows through jit as a pytree: metadata static,
    buffer traced."""
    x = rng.standard_normal(19)  # ragged
    dx = DistributedArray.to_dist(x)

    @jax.jit
    def f(d):
        return (d * 2 + 1).copy()

    out = f(dx)
    assert isinstance(out, DistributedArray)
    assert out.local_shapes == dx.local_shapes
    np.testing.assert_allclose(out.asarray(), 2 * x + 1, rtol=1e-12)
    # second call hits the cache (same treedef)
    out2 = f(out)
    np.testing.assert_allclose(out2.asarray(), 4 * x + 3, rtol=1e-12)


def test_stacked_pytree_roundtrip(rng):
    a = rng.standard_normal(24)
    b = rng.standard_normal((6, 5))
    s = StackedDistributedArray([DistributedArray.to_dist(a),
                                 DistributedArray.to_dist(b)])

    @jax.jit
    def f(st):
        return st * 3.0

    out = f(s)
    assert isinstance(out, StackedDistributedArray)
    np.testing.assert_allclose(
        out.asarray(), 3 * np.concatenate([a, b.ravel()]), rtol=1e-12)


def test_masked_solve_single_program(rng):
    """A masked (sub-communicator) fused CG jits into ONE program whose
    per-group scalars stay on device (ref: each MPI group would run its
    own allreduce stream)."""
    P = len(jax.devices())
    half = P // 2 or 1
    mask = [i // half for i in range(P)]
    mats = []
    for _ in range(P):  # one block per shard: groups stay decoupled
        a = rng.standard_normal((4, 4))
        mats.append(a @ a.T + 4 * np.eye(4))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats],
                      mask=mask)
    import scipy.linalg as spla
    dense = spla.block_diag(*mats)
    xtrue = rng.standard_normal(4 * P)
    dy = DistributedArray.to_dist(dense @ xtrue, mask=mask)
    x0 = DistributedArray.to_dist(np.zeros(4 * P), mask=mask)

    fn = jax.jit(lambda y, x: _cg_fused(Op, y, x, 1e-13, niter=100)[0])
    got = fn(dy, x0)
    np.testing.assert_allclose(got.asarray(), xtrue, rtol=1e-6, atol=1e-8)
    # the loop is a single while op, not an unrolled chain
    jaxpr = jax.make_jaxpr(
        lambda y, x: _cg_fused(Op, y, x, 1e-13, niter=100)[0])(
        dy, x0)
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert "while" in prims


def test_stacked_solver_jit(rng):
    """CGLS over a stacked data space inside one jit (the combination
    VERDICT flagged as untested). Note masks are NOT mixed in: per-group
    reductions model independent problems, and a Gradient regularizer
    couples the groups — the reference's mask contract excludes that."""
    mats = []
    for _ in range(8):
        a = rng.standard_normal((4, 4))
        mats.append(a @ a.T + 4 * np.eye(4))
    Bop = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    Gop = MPIGradient((32,), dtype=np.float64)
    SG = MPIStackedVStack([Bop, 0.3 * Gop])
    xtrue = rng.standard_normal(32)
    dx = DistributedArray.to_dist(xtrue)
    data = SG.matvec(dx)

    fn = jax.jit(lambda y, x: _cgls_fused(SG, y, x, 0.0, 0.0,
                                          niter=400)[0])
    got = fn(data, dx.zeros_like())
    import scipy.linalg as spla
    dense_B = spla.block_diag(*mats)
    DG = np.zeros((32, 32))
    for i in range(1, 31):
        DG[i, i - 1], DG[i, i + 1] = -0.5, 0.5
    dense = np.vstack([dense_B, 0.3 * DG])
    y_full = np.concatenate([dense_B @ xtrue, 0.3 * DG @ xtrue])
    xs = np.linalg.lstsq(dense, y_full, rcond=None)[0]
    np.testing.assert_allclose(got.asarray(), xs, rtol=1e-5, atol=1e-6)


def test_operator_inside_jit_composition(rng):
    """Composed lazy operators trace once inside an outer jit with no
    host callbacks."""
    mats = [rng.standard_normal((4, 4)) for _ in range(8)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    C = 2.0 * Op.H @ Op + Op.T @ Op.conj()

    @jax.jit
    def f(d):
        return C.matvec(d)

    x = rng.standard_normal(32)
    dx = DistributedArray.to_dist(x)
    import scipy.linalg as spla
    D = spla.block_diag(*mats)
    expected = 2.0 * D.T @ (D @ x) + D.T @ (D @ x)
    np.testing.assert_allclose(f(dx).asarray(), expected, rtol=1e-10)


def test_fused_solver_no_host_sync_per_iter(rng):
    """The fused CGLS lowers to one while loop: iteration count in the
    HLO is data-dependent, not unrolled (SURVEY §3.2's 4-host-syncs-per-
    iteration pathology eliminated)."""
    P = len(jax.devices())
    mats = [rng.standard_normal((4, 4)) for _ in range(P)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    dy = DistributedArray.to_dist(rng.standard_normal(4 * P))
    x0 = dy.zeros_like()
    hlo = jax.jit(
        lambda y, x: _cgls_fused(Op, y, x, 0.0, 0.0, niter=50)[0]._arr
    ).lower(dy, x0).compile().as_text()
    assert hlo.count("while") >= 1
    # 50 iterations must NOT appear as 50 unrolled GEMM pairs
    assert hlo.count("dot(") < 20 if "dot(" in hlo else True


def test_ragged_vectors_through_fused_solver(rng):
    """Ragged (pad-to-max) vectors keep logical semantics through the
    on-device loop: padding never leaks into reductions."""
    sizes = [5, 3, 4, 2, 5, 3, 4, 2]
    mats = []
    for s in sizes:
        a = rng.standard_normal((s, s))
        mats.append(a @ a.T + s * np.eye(s))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    import scipy.linalg as spla
    dense = spla.block_diag(*mats)
    n = sum(sizes)
    xtrue = rng.standard_normal(n)
    dy = DistributedArray.to_dist(dense @ xtrue,
                                  local_shapes=Op.local_shapes_n)
    fn = jax.jit(lambda y, x: _cg_fused(Op, y, x, 1e-13, niter=120)[0])
    got = fn(dy, dy.zeros_like())
    np.testing.assert_allclose(got.asarray(), xtrue, rtol=1e-6, atol=1e-8)


def test_fused_cgls_collective_schedule_is_scalar_only(rng):
    """The flagship fused CGLS program's ONLY collectives are a handful
    of scalar all-reduces (the psum'd solver scalars): no all-gather, no
    per-iteration data movement — the single-XLA-program redesign win
    (SURVEY §3.2). Pinned so layout regressions cannot sneak in."""
    import jax.numpy as jnp
    from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
    from pylops_mpi_tpu.ops.local import MatrixMult
    from pylops_mpi_tpu.solvers.basic import _cgls_fused, _cgls_fused_normal
    from pylops_mpi_tpu.utils import collective_report

    P = len(jax.devices())  # aligned layouts: the 3-scalar pin is
    # the even-split schedule; ragged repacks legitimately add reduces
    blocks = [rng.standard_normal((32, 32)).astype(np.float32)
              for _ in range(P)]
    y = DistributedArray.to_dist(
        rng.standard_normal(32 * P).astype(np.float32))
    for cd, solver in ((None, _cgls_fused), (jnp.bfloat16,
                                             _cgls_fused_normal)):
        Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                           for b in blocks], compute_dtype=cd)
        if cd is not None and not Op.has_fused_normal:
            solver = _cgls_fused
        rep = collective_report(
            lambda yy, xx: solver(Op, yy, xx, 0.0, 0.0, niter=20)[0].array,
            y, y.zeros_like())
        # NOTHING but scalar all-reduces — any other collective kind
        # (gather, permute, reduce-scatter, ...) is a layout regression
        assert set(rep) <= {"all-reduce"}, rep
        ar = rep.get("all-reduce", {"count": 0, "max_bytes": 0})
        # the psum'd solver scalars: 3 on current jax; the 0.4.x
        # compiler CSEs one fewer and emits 4 — both are the same
        # scalar-only schedule (the regression this pins is a DATA-sized
        # collective appearing, caught by max_bytes and the kind check)
        assert 3 <= ar["count"] <= 4, rep
        assert ar["max_bytes"] <= 16, rep     # each is one scalar


@pytest.mark.parametrize("momentum", [False, True])
def test_fused_ista_collective_schedule_is_scalar_only(rng, momentum):
    """The fused ISTA/FISTA program, like fused CGLS, moves no data
    between shards — its only collectives are the scalar all-reduces of
    the step/cost/update norms."""
    import jax.numpy as jnp
    from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
    from pylops_mpi_tpu.ops.local import MatrixMult
    from pylops_mpi_tpu.solvers.sparsity import _ista_fused, _THRESHF
    from pylops_mpi_tpu.utils import collective_report

    P = len(jax.devices())
    blocks = [rng.standard_normal((16, 16)).astype(np.float32)
              for _ in range(P)]
    Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks])
    y = DistributedArray.to_dist(
        rng.standard_normal(16 * P).astype(np.float32))

    def run(yy, xx):
        return _ista_fused(Op, yy, xx, 0.2, 0.1, 0.0,
                           jnp.ones(10, dtype=jnp.float32), niter=10,
                           threshf=_THRESHF["soft"],
                           momentum=momentum)[0].array

    rep = collective_report(run, y, y.zeros_like())
    assert set(rep) == {"all-reduce"}, rep
    ar = rep["all-reduce"]
    # at least one cross-shard reduction must exist (dropping the psum
    # entirely would be a different, worse regression), and none may
    # exceed scalar size
    assert 1 <= ar["count"] <= 6, rep
    assert ar["max_bytes"] <= 16, rep


# ------------------------------------------- block CGLS, one-sweep schedule
def _block_problem(rng, K=4, m=256, n=128):
    P = len(jax.devices())
    Op = MPIBlockDiag([MatrixMult(
        (rng.standard_normal((m, n)) / np.sqrt(n)).astype(np.float32)
        + 4 * np.eye(m, n, dtype=np.float32), dtype=np.float32)
        for _ in range(P)])
    y = DistributedArray(global_shape=(P * m, K), dtype=np.float32)
    y[:] = rng.standard_normal((P * m, K)).astype(np.float32)
    x0 = DistributedArray(global_shape=(P * n, K), dtype=np.float32)
    return Op, y, x0


@pytest.mark.parametrize("normal", [True, False],
                         ids=["one_sweep", "classic"])
def test_block_cgls_program_for_a_tpu_holds_one_kernel_in_its_loop(
        rng, monkeypatch, normal):
    """The ``block_cgls`` program lowered for a TPU (from here, no chip:
    ``lowering_platforms``; Pallas is asked to compile, not to
    interpret). One-sweep: ONE Mosaic call, ``pmt_normal``, inside the
    ``while`` body and no ``bmn,bnk`` contraction there — the two
    ``dot_general``s left are the pre-loop ``Op x0`` and ``Opᴴ s``.
    Classic: no kernel, and the loop holds the block matvec and
    rmatvec."""
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    from pylops_mpi_tpu.solvers.block import _block_cgls_fused
    Op, y, x0 = _block_problem(rng)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    fn = jax.jit(lambda op, yy, xx: _block_cgls_fused(
        op, yy, xx, 0.0, 0.0, niter=5, normal=normal)[0].array)
    text = fn.trace(Op, y, x0).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.while") == 1
    loop = text.index("stablehlo.while")
    dots = [m.start() for m in re.finditer(r"stablehlo\.dot_general", text)]
    calls = [m.start() for m in re.finditer(r"@tpu_custom_call", text)]
    if normal:
        assert len(calls) == 1 and calls[0] > loop
        assert re.findall(r"kernel_name\W+(\w+)", text) == ["pmt_normal"]
        assert len(dots) == 2 and all(d < loop for d in dots)
    else:
        assert not calls and "pmt_normal" not in text
        assert len(dots) == 5
        assert sum(d > loop for d in dots) == 2


def test_use_normal_is_part_of_the_fused_and_aot_keys(rng, monkeypatch):
    """A flip of the schedule never meets a stale executable: the
    ``_get_fused`` key and the AOT bank's key (the same tuple behind
    the operator's structural signature) carry ``use_normal``, so with
    the AOT tier armed each schedule compiles once — also for a fresh
    operator instance of the same signature — and answers as the
    unarmed program of its own schedule does."""
    from pylops_mpi_tpu import aot
    from pylops_mpi_tpu.solvers import basic
    Op, y, _ = _block_problem(rng, K=2, m=32, n=24)
    mats = [np.asarray(b) for b in Op._batched]
    fresh = lambda: MPIBlockDiag([MatrixMult(m, dtype=np.float32)
                                  for m in mats])
    solve = lambda op, normal: np.asarray(pmt.block_cgls(
        op, y, niter=4, tol=0.0, normal=normal)[0].asarray())
    pmt.clear_fused_cache()
    plain = {normal: solve(Op, normal) for normal in (False, True)}
    keys = [k for k in basic._FUSED_CACHE if k[0] == id(Op)]
    assert sorted(k[2] for k in keys) == [False, True]
    assert keys[0][:2] == keys[1][:2] == (id(Op), "block_cgls")
    assert keys[0][3:] == keys[1][3:]

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    monkeypatch.delenv("PYLOPS_MPI_TPU_AOT_CACHE", raising=False)
    try:
        pmt.clear_fused_cache()
        aot.clear_memory()
        aot.reset_compile_count()
        seen = []
        for normal in (False, True, False, True):
            np.testing.assert_array_equal(solve(fresh(), normal),
                                          plain[normal])
            seen.append(aot.compile_count())
        assert seen == [1, 2, 2, 2]
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        aot.clear_memory()
        aot.reset_compile_count()
        pmt.clear_fused_cache()
