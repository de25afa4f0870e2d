"""``cgls(normal=None)``: the sweep schedule is what the operator and
the input allow (ISSUE 26).

One resolver (``solvers/basic._resolve_normal``) serves ``cgls``,
``cgls_guarded`` and ``resilient_solve``; the operator's answer
(``prefers_fused_normal``) is a pure function of what it observes —
backend, block stack, mesh, tile, input rank and dtype; the tile rule
is the chip's table (PERF.md section 6, PR 26). On the CPU the default
compiles the classic program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.diagnostics import metrics, trace
from pylops_mpi_tpu.ops import pallas_kernels as pk
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.resilience import resilient_solve
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo

NDEV = len(jax.devices())


def _problem(rng, n=16, dtype=np.float32, nblk=NDEV, **kw):
    blocks = [(rng.standard_normal((n, n)) + 4 * np.eye(n)).astype(dtype)
              for _ in range(nblk)]
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=dtype) for b in blocks],
                          **kw)
    y = pmt.DistributedArray.to_dist(
        rng.standard_normal(nblk * n).astype(dtype))
    return Op, y


# ------------------------------------------------------------ (a) resolver
def _via_cgls(Op, y, **kw):
    return pmt.cgls(Op, y, niter=4, tol=0.0, **kw)[0].asarray()


def _via_guarded(Op, y, **kw):
    return basic.cgls_guarded(Op, y, niter=4, tol=0.0, **kw)[0].asarray()


def _via_resilient(Op, y, **kw):
    return resilient_solve(Op, y, solver="cgls", niter=4, tol=0.0,
                           **kw).x.asarray()


def _columns(y, K):
    """``y`` and scaled copies of it as the ``(rows, K)`` block vector
    the block solvers take."""
    yb = pmt.DistributedArray(global_shape=(y.global_shape[0], K),
                              dtype=y.dtype)
    yb[:] = np.stack([(j + 1) * y.asarray() for j in range(K)], axis=1)
    return yb


def _via_block(Op, y, **kw):
    return pmt.block_cgls(Op, _columns(y, 2), niter=4, tol=0.0,
                          **kw)[0].asarray()[:, 0]


def _via_block_k1(Op, y, **kw):
    return pmt.block_cgls(Op, _columns(y, 1), niter=4, tol=0.0,
                          **kw)[0].asarray()[:, 0]


# entry -> (the call, its counters' solver, its span, the trailing shape
# of the vector the operator is asked about); the K == 1 branch of
# block_cgls asks like every block solve, then runs (and counts as) the
# single-RHS program with the answer
_ENTRIES = {
    "cgls": (_via_cgls, "cgls", "solver.cgls", ()),
    "cgls_guarded": (_via_guarded, "cgls", "solver.cgls", ()),
    "resilient_solve": (_via_resilient, "cgls", "solver.cgls", ()),
    "block_cgls": (_via_block, "block_cgls", "solver.block_cgls", (2,)),
    "block_cgls_k1": (_via_block_k1, "cgls", "solver.block_cgls", (1,)),
}


@pytest.mark.parametrize("entry", list(_ENTRIES))
@pytest.mark.parametrize("answer,normal,one_sweep", [
    (True, None, True), (False, None, False),
    (True, False, False), (False, True, True)],
    ids=["yes", "no", "false_beats_yes", "true_beats_no"])
def test_one_resolver_for_every_entry(monkeypatch, rng, entry, answer,
                                      normal, one_sweep):
    """The operator is asked only when the caller said nothing; what
    was resolved shows in the kernel run, the counter and the span."""
    call, solver, span, cols = _ENTRIES[entry]
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    metrics.clear_metrics()
    trace.clear_events()
    asked, applied = [], []
    monkeypatch.setattr(
        pmt.MPIBlockDiag, "prefers_fused_normal",
        lambda self, x: asked.append(x.global_shape) or answer)
    real = pmt.MPIBlockDiag.normal_matvec
    monkeypatch.setattr(
        pmt.MPIBlockDiag, "normal_matvec",
        lambda self, x: applied.append(1) or real(self, x))
    Op, y = _problem(rng)
    x = call(Op, y, **({} if normal is None else {"normal": normal}))
    assert asked == ([] if normal is not None else [y.global_shape + cols])
    assert bool(applied) == one_sweep
    counters = metrics.snapshot()["counters"]
    assert counters.get(f"solver.{solver}.one_sweep", 0) == int(one_sweep)
    assert counters[f"solver.{solver}.solves"] == 1
    spans = [e for e in trace.get_events() if e["name"] == span]
    assert [e["args"]["normal"] for e in spans] == [one_sweep]
    ref = pmt.cgls(Op, y, niter=4, tol=0.0, normal=False)[0]
    np.testing.assert_allclose(x, ref.asarray(), rtol=2e-4, atol=1e-5)
    metrics.clear_metrics()
    trace.clear_events()


def test_unfused_default_stays_classic_and_does_not_raise(monkeypatch, rng):
    monkeypatch.setattr(pmt.MPIBlockDiag, "prefers_fused_normal",
                        lambda self, x: True)
    Op, y = _problem(rng)
    seen = []
    x = pmt.cgls(Op, y, niter=4, tol=0.0, fused=False,
                 callback=lambda v: seen.append(1))[0]
    ref = pmt.cgls(Op, y, niter=4, tol=0.0, normal=False)[0]
    np.testing.assert_allclose(x.asarray(), ref.asarray(), rtol=2e-4,
                               atol=1e-5)
    assert len(seen) == 4
    assert basic._resolve_normal(Op, y, None, use_fused=False) is False
    with pytest.raises(ValueError, match="normal=True requires"):
        pmt.cgls(Op, y, niter=2, normal=True, fused=False)


def test_operator_without_the_method_is_classic(rng):
    class Bare:
        shape = (4, 4)

    assert basic._resolve_normal(Bare(), None, None) is False
    assert basic._resolve_normal(Bare(), None, True) is True
    Op, y = _problem(rng)
    assert pmt.MPILinearOperator.prefers_fused_normal(Op, y) is False


# ------------------------------------------------- (b) the operator's answer
def _flagship(**kw):
    """One flagship block (4096 x 4096 f32, tile 256) on one device."""
    return pmt.MPIBlockDiag(
        [MatrixMult(np.zeros((4096, 4096), np.float32), dtype=np.float32)],
        mesh=pmt.make_mesh(1), **kw)


def _vec(Op, dtype=np.float32, ncol=None):
    shape = Op.shape[1] if ncol is None else (Op.shape[1], ncol)
    return pmt.DistributedArray(global_shape=shape, mesh=Op.mesh,
                                dtype=dtype)


def _small(mesh=None, otherdims=(), n=512, **kw):
    """Blocks of one 1 MiB tile each: small, and on the fast side."""
    mesh = mesh if mesh is not None else pmt.make_mesh(1)
    nblk = int(mesh.devices.size)
    return pmt.MPIBlockDiag(
        [MatrixMult(np.zeros((n, n), np.float32), otherdims=otherdims,
                    dtype=np.float32) for _ in range(nblk)],
        mesh=mesh, **kw)


def test_on_the_cpu_the_answer_is_no():
    Op = _flagship()
    assert Op.has_fused_normal            # the kernel, interpreted here
    assert Op.prefers_fused_normal(_vec(Op)) is False
    assert pk.normal_matvec_pays(Op._batched) is False


def _hetero():
    return pmt.MPIBlockDiag(
        [MatrixMult(np.zeros((512 + 8 * i, 512), np.float32),
                    dtype=np.float32) for i in range(2)],
        mesh=pmt.make_mesh(1))


def _complex_blocks():
    return pmt.MPIBlockDiag(
        [MatrixMult(np.zeros((512, 512), np.complex64),
                    dtype=np.complex64)], mesh=pmt.make_mesh(1))


# case -> (operator, keywords of its input vector, the answer)
_ANSWERS = {
    "flagship": (_flagship, {}, True),
    "bf16_blocks": (lambda: _flagship(compute_dtype=jnp.bfloat16), {}, True),
    "small_fast_tile": (_small, {}, True),
    "two_d_input": (_small, {"ncol": 4}, True),
    "flagship_k16": (_flagship, {"ncol": 16}, True),
    "flagship_k64": (_flagship, {"ncol": 64}, True),
    "flagship_k65": (_flagship, {"ncol": 65}, False),    # not measured
    "slow_tile_k16": (lambda: _small(n=256), {"ncol": 16}, False),
    "complex_input": (_small, {"dtype": np.complex64}, False),
    "f64_input": (_small, {"dtype": np.float64}, False),
    # blocks with columns of their own (_batched_k > 1) keep the pair
    "multi_rhs_blocks": (lambda: _small(otherdims=(2,)), {}, False),
    "multi_rhs_k16": (lambda: _small(otherdims=(2,)), {"ncol": 16}, False),
    "two_d_mesh": (lambda: _small(mesh=pmt.make_mesh_2d(4)), {}, False),
    "two_sweep_forced": (lambda: _small(normal_path="two_sweep"), {}, False),
    "slow_tile": (lambda: _small(n=256), {}, False),   # 256 KiB a tile
    "heterogeneous": (_hetero, {}, False),
    "complex_blocks": (_complex_blocks, {"dtype": np.complex64}, False),
}


@pytest.mark.parametrize("case", list(_ANSWERS))
def test_operator_answer_on_a_tpu(monkeypatch, case):
    """The answer as a pure function of what the operator observes,
    with the backend reading ``tpu`` (nothing is compiled or run)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build, vec_kw, expected = _ANSWERS[case]
    Op = build()
    x = _vec(Op, **vec_kw)
    assert Op.prefers_fused_normal(x) is expected
    assert basic._resolve_normal(Op, x, None) is expected


@pytest.mark.parametrize("build,has", [
    (lambda: _small(), True),
    (lambda: _small(compute_dtype=jnp.bfloat16), True),
    (lambda: _complex_blocks(), False)],
    ids=["f32_blocks", "bf16_storage", "complex_blocks"])
def test_has_fused_normal_means_one_thing_on_every_backend(monkeypatch,
                                                           build, has):
    """``has_fused_normal``: a Pallas kernel exists for these blocks —
    the CPU's answer is the chip's (only *pays* reads the backend)."""
    Op = build()
    assert Op.has_fused_normal is has
    x = _vec(Op, dtype=Op.dtype)
    kernel = Op._normal_kernel_for(x)
    assert (kernel is pk.batched_normal_matvec) is has
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert Op.has_fused_normal is has
    assert Op._normal_kernel_for(x) is kernel
    assert build().has_fused_normal is has     # and built as on a TPU


def test_tuned_two_sweep_plan_answers_no(monkeypatch):
    from pylops_mpi_tpu.tuning import plan as tuneplan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuneplan, "get_plan",
                        lambda *a, **k: {"normal_path": "two_sweep"})
    Op = _small()
    assert Op.prefers_fused_normal(_vec(Op)) is False


# ------------------------------------------------------- (c) the tile rule
@pytest.mark.parametrize("m,n,itemsize,tile,fast", [
    # what _pick_tile hands out, with the chip's reading of one sweep
    # over two (v5e; PERF.md section 6, PR 26)
    (1024, 1024, 4, 512, True),      # 1.53 x
    (2048, 2048, 4, 512, True),      # 1.75 x
    (4096, 4096, 4, 256, True),      # 1.86 x, the flagship
    (8192, 8192, 4, 128, True),      # 2.06 x
    (16384, 16384, 4, 64, True),     # 2.01 x
    (4096, 4096, 2, 256, True),      # bf16 storage, 1.61 x
    (512, 512, 4, 512, True),        # one 1 MiB tile a block, 1.23 x
    (256, 256, 4, 256, False),       # one 256 KiB tile a block, 0.73 x
    (1000, 1000, 4, 8, False),       # 0.22 x
    (4104, 4104, 4, 8, False),       # 0.55 x
    (24, 16, 4, 8, False)])
def test_tile_rule_table(m, n, itemsize, tile, fast):
    A = jax.ShapeDtypeStruct(
        (2, m, n), {4: jnp.float32, 2: jnp.bfloat16}[itemsize])
    tm, stream = pk._tile_args(A)
    assert (tm, stream) == (tile, itemsize < 4)
    assert pk._tile_beats_two_sweeps(tm, n, itemsize) is fast


@pytest.mark.parametrize("tm,n,itemsize,fast", [
    # forced tiles, as measured: the tile's bytes decide
    (128, 4096, 4, True),    # 2 MiB, 1.86 x
    (64, 4096, 4, True),     # 1 MiB, 1.78 x
    (32, 4096, 4, True),     # 512 KiB, 1.39 x
    (16, 4096, 4, False),    # 256 KiB, 0.94 x
    (8, 4096, 4, False),     # 0.53 x
    (64, 2048, 4, True),     # 512 KiB, 1.36 x
    (32, 2048, 4, False),    # 256 KiB, 0.995 x
    (128, 1024, 4, True),    # 512 KiB, 1.30 x
    (64, 1024, 4, False),    # 256 KiB, 0.97 x
    (16, 1024, 4, False),    # 0.36 x
    (256, 512, 4, True),     # 512 KiB, 1.12 x
    (128, 4096, 2, True),    # bf16, 1 MiB, 1.43 x
    (64, 4096, 2, True)])    # bf16, 512 KiB, 1.17 x
def test_tile_rule_follows_the_tile_bytes(tm, n, itemsize, fast):
    assert pk._tile_beats_two_sweeps(tm, n, itemsize) is fast


@pytest.mark.parametrize("n,itemsize,cols,pays", [
    # blocks n x n, columns a block -> one sweep over two as the chip
    # read it (v5e; PERF.md section 6, PR 31), or None where the rule
    # answers from its nearest measured neighbours
    (4096, 4, 1, True),       # 2.01 x, 4 MiB tiles: the flagship
    (4096, 4, 2, True),       # 2.05 x
    (4096, 4, 4, True),       # 2.05 x
    (4096, 4, 8, True),       # 2.05 x
    (4096, 4, 16, True),      # 2.08 x: the service's widest bucket
    (4096, 4, 32, True),      # 1.92 x
    (4096, 4, 64, True),      # 1.33 x
    (4096, 4, 65, False),     # not measured
    (4096, 4, 128, False),    # 1.01 x: a tie keeps the classic body
    (4096, 4, 256, False),    # 0.98 x
    (4096, 2, 1, True),       # bf16 storage, 1.99 x
    (4096, 2, 16, True),      # bf16 storage, 1.99 x
    (1024, 4, 1, True),       # 2 MiB tiles, 1.98 x
    (1024, 4, 16, True),      # 1.89 x
    (512, 4, 1, True),        # 1 MiB tiles, 1.84 x
    (512, 4, 16, True),       # 1.66 x
    (512, 4, 128, False),     # the column rule holds at every tile
    (256, 4, 1, False),       # 256 KiB tiles, 1.15 x: PR 26's tile rule
    (256, 4, 16, False),      # 1.04 x
    (1000, 4, 16, False)])    # 8-row tiles
def test_pays_rule_table(monkeypatch, n, itemsize, cols, pays):
    """``normal_matvec_pays``: a compiled kernel, a row tile of 512 KiB
    or more AND at most 64 columns a block — each line as measured."""
    A = jax.ShapeDtypeStruct(
        (2, n, n), {4: jnp.float32, 2: jnp.bfloat16}[itemsize])
    assert pk.normal_matvec_pays(A, cols) is False          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk.normal_matvec_pays(A, cols) is pays
    tm, _ = pk._tile_args(A)
    assert pays is (pk._tile_beats_two_sweeps(tm, n, itemsize)
                    and pk._cols_beat_two_sweeps(cols))


# -------------------------------------------------------------- (d) drift
def _spectrum_blocks(rng, s):
    """One f32 block ``U diag(s) Vt`` a device, and their operator."""
    n = len(s)
    blocks = []
    for _ in range(NDEV):
        U, _ = np.linalg.qr(rng.standard_normal((n, n)))
        V, _ = np.linalg.qr(rng.standard_normal((n, n)))
        blocks.append(((U * s) @ V.T).astype(np.float32))
    return (pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                              for b in blocks]), blocks)


def _drift(rng, cond, niter, spacing, n=256):
    """Error to the true model of ``cgls(normal=True)`` over that of
    ``normal=False``: f32 blocks ``U diag(s) Vt`` with singular values
    spaced over ``[1/cond, 1]``, f32 vectors."""
    s = (np.linspace(1, 1 / cond, n) if spacing == "lin"
         else np.logspace(0, -np.log10(cond), n))
    Op, blocks = _spectrum_blocks(rng, s)
    xtrue = rng.standard_normal(NDEV * n).astype(np.float32)
    y = pmt.DistributedArray.to_dist(np.concatenate([
        b.astype(np.float64) @ xtrue[i * n:(i + 1) * n]
        for i, b in enumerate(blocks)]).astype(np.float32))
    err = {}
    for normal in (True, False):
        x = pmt.cgls(Op, y, niter=niter, tol=0.0, normal=normal)[0]
        assert x.dtype == np.float32
        err[normal] = float(np.linalg.norm(x.asarray() - xtrue)
                            / np.linalg.norm(xtrue))
    return err[True] / err[False]


@pytest.mark.parametrize("niter", [30, 400])
@pytest.mark.parametrize("cond", [3, 100, 1000])
def test_one_sweep_drift_is_within_a_tenth(rng, cond, niter):
    """f32, evenly spaced spectrum (ISSUE 26's probe): the gradient
    recurrence's error to the true model stays within 1.1 x the
    classic schedule's (seen: at most 1.04 x over five seeds) — the
    reason it no longer has to be asked for."""
    assert _drift(rng, cond, niter, "lin") <= 1.1


@pytest.mark.parametrize("niter,band", [(30, 1.01), (400, 1.5)])
def test_one_sweep_scatters_about_the_classic_floor(rng, niter, band):
    """Log-spaced spectrum at cond 100: before the f32 floor (30
    iterations) the schedules agree to a part in a thousand; sitting
    on it (400) single seeds scatter to BOTH sides (seen: 0.85-1.27 x)
    — rounding noise at a floor the conditioning sets, not a drift
    away from it."""
    assert 1 / band <= _drift(rng, 100, niter, "log") <= band


def _block_drift(rng, damp, guards, precond, K=3, n=64, cond=100, niter=30):
    """Per column: error of ``block_cgls(normal=True)`` to the damped
    least-squares solution over the classic body's, and the largest
    distance between the two answers; f32 blocks with an evenly spaced
    spectrum, f64 dense solutions."""
    Op, blocks = _spectrum_blocks(rng, np.linspace(1, 1 / cond, n))
    Xtrue = rng.standard_normal((NDEV * n, K))
    Y = np.concatenate([b.astype(np.float64) @ Xtrue[i * n:(i + 1) * n]
                        for i, b in enumerate(blocks)])
    want = np.concatenate([
        np.linalg.solve(b.astype(np.float64).T @ b.astype(np.float64)
                        + damp ** 2 * np.eye(n),
                        b.astype(np.float64).T @ Y[i * n:(i + 1) * n])
        for i, b in enumerate(blocks)])
    y = pmt.DistributedArray(global_shape=Y.shape, dtype=np.float32)
    y[:] = Y.astype(np.float32)
    M = None
    if precond:
        from pylops_mpi_tpu.ops.precond import BlockJacobiPrecond
        M = BlockJacobiPrecond.from_block_diag(Op, normal=True)
    got = {}
    for normal in (True, False):
        out = pmt.block_cgls(Op, y, niter=niter, tol=0.0, damp=damp,
                             guards=guards, M=M, normal=normal)
        assert out[2] == niter and out[0].dtype == np.float32
        got[normal] = out[0].asarray().astype(np.float64)
    err = {k: np.linalg.norm(v - want, axis=0) / np.linalg.norm(want, axis=0)
           for k, v in got.items()}
    apart = (np.linalg.norm(got[True] - got[False], axis=0)
             / np.linalg.norm(want, axis=0))
    return err[True] / err[False], apart, err[False]


@pytest.mark.parametrize("precond", [False, True], ids=["plain", "M"])
@pytest.mark.parametrize("guards", [False, True], ids=["bare", "guards"])
@pytest.mark.parametrize("damp", [0.0, 0.1])
def test_block_one_sweep_drift_column_by_column(rng, damp, guards, precond):
    """``block_cgls(normal=True)`` against the classic body, every
    column on its own: the band ``cgls`` is held to above (within 1.1 x
    the classic error to the solution), with and without ``damp``,
    guards and ``M``; where a preconditioned solve has reached the f32
    floor the two answers are that floor apart."""
    ratio, apart, floor = _block_drift(rng, damp, guards, precond)
    assert ratio.shape == (3,)
    assert np.all(ratio <= 1.1) or np.all(apart <= 2 * floor)
    assert np.all(apart <= np.maximum(2 * floor, 1e-5))


@pytest.mark.parametrize("guards", [False, True], ids=["bare", "guards"])
def test_block_one_sweep_keeps_a_frozen_column_frozen(rng, guards):
    """Columns freeze one by one in both bodies: a zero right-hand side
    sits on its floor from the start (its answer stays exactly zero,
    its history flat), a well-conditioned column reaches its
    machine-precision floor inside the run while a column 1e4 x larger
    still iterates — and the one-sweep answers stay with the classic
    ones column by column."""
    n, niter = 32, 40
    Op = pmt.MPIBlockDiag([MatrixMult(       # the flagship's family
        (rng.standard_normal((n, n)) / np.sqrt(n)
         + 4 * np.eye(n)).astype(np.float32), dtype=np.float32)
        for _ in range(NDEV)])
    Y = rng.standard_normal((NDEV * n, 3)).astype(np.float32)
    Y[:, 0] = 0.0
    Y[:, 2] *= 1e4
    y = pmt.DistributedArray(global_shape=Y.shape, dtype=np.float32)
    y[:] = Y
    out = {normal: pmt.block_cgls(Op, y, niter=niter, tol=0.0,
                                  guards=guards, normal=normal)
           for normal in (True, False)}
    for normal, (x, istop, iiter, kold, r2, cost) in out.items():
        assert iiter == niter and cost.shape == (niter + 1, 3)
        assert not np.any(x.asarray()[:, 0]) and not np.any(cost[:, 0])
        # frozen on its floor: the history stops moving before the end
        assert cost[-1, 1] == cost[-10, 1] and cost[-1, 2] == cost[-10, 2]
        assert np.all(np.isfinite(x.asarray()))
    xa, xb = (out[k][0].asarray() for k in (True, False))
    scale = np.linalg.norm(xb, axis=0)
    apart = np.linalg.norm(xa - xb, axis=0)
    assert np.all(apart[1:] <= 2e-5 * scale[1:])


# ------------------------------------------------- (e) the CPU's program
def _program_of(Op, *args):
    """Optimized HLO of the one fused program ``cgls`` compiled for
    ``Op`` (the jit behind its ``_FUSED_CACHE`` entry)."""
    (fn, _, _), = [v for k, v in basic._FUSED_CACHE.items()
                   if k[0] == id(Op)]
    bound = fn.__kwdefaults__
    return hlo.compiled_hlo(bound["_jfn"], bound["_op"], *args)


def test_cpu_default_compiles_the_classic_program(rng):
    texts = {}
    for normal in (None, False, True):
        r = np.random.default_rng(7)
        Op, y = _problem(r)
        assert Op.has_fused_normal
        kw = {} if normal is None else {"normal": normal}
        pmt.cgls(Op, y, niter=3, tol=0.0, **kw)
        texts[normal] = hlo.strip_provenance(
            _program_of(Op, y, y.zeros_like(), 0.0, 0.0))
    assert texts[None] == texts[False]
    assert texts[True] != texts[False]


def test_cpu_default_of_block_cgls_compiles_the_classic_program():
    """``block_cgls`` on the CPU: saying nothing compiles the program
    ``normal=False`` compiles (and that program holds no kernel), the
    one-sweep schedule is another program under another cache key."""
    texts, keys = {}, {}
    for normal in (None, False, True):
        Op, y = _problem(np.random.default_rng(7))
        yb = _columns(y, 2)
        kw = {} if normal is None else {"normal": normal}
        pmt.block_cgls(Op, yb, niter=3, tol=0.0, **kw)
        (keys[normal],) = [k for k in basic._FUSED_CACHE if k[0] == id(Op)]
        texts[normal] = hlo.strip_provenance(
            _program_of(Op, yb, yb.zeros_like(), 0.0, 0.0))
    assert texts[None] == texts[False]
    assert texts[True] != texts[False]
    assert "pmt_normal" not in texts[None]
    assert keys[None][1:] == keys[False][1:]
    assert keys[True][1:] != keys[False][1:]


def test_fused_cache_key_separates_the_schedules(rng):
    """One operator, one block right-hand side, both schedules: two
    cache entries (guarded and unguarded alike), each reused by its own
    schedule's next solve."""
    Op, y = _problem(rng)
    yb = _columns(y, 2)
    for guards in (False, True):
        pmt.clear_fused_cache()
        for normal in (False, True, False, True):
            pmt.block_cgls(Op, yb, niter=2, tol=0.0, normal=normal,
                           guards=guards)
        keys = [k for k in basic._FUSED_CACHE if k[0] == id(Op)]
        assert len(keys) == 2
        assert sorted(k[2] for k in keys) == [False, True]
        assert {k[:2] for k in keys} == {(id(Op), "block_cgls")}
