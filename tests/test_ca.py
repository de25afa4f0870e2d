"""Communication-avoiding solver tier (solvers/ca.py).

The CA PR's acceptance pins: ``PYLOPS_MPI_TPU_CA=off`` compiles the
bit-identical classic program (and the stall seam off contributes
nothing to it); the pipelined engine carries EXACTLY ONE all-reduce
per while-loop body vs ≥2 classic, HLO-pinned via
``utils/hlo.count_reductions``; pipelined and s-step land on the
classic fixed point across engines × precisions × ``M=`` with
iteration parity; the s-step basis-conditioning guard falls back to
the pipelined engine mid-solve on breakdown; per-column freeze and
guard verdicts survive the CA engines; segmented kill/resume is
trajectory-identical per CA mode and a resume under a DIFFERENT mode
refuses.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.ops import _precision as PR
from pylops_mpi_tpu.ops.precond import JacobiPrecond, BlockJacobiPrecond
from pylops_mpi_tpu.resilience import status as rstatus
from pylops_mpi_tpu.solvers import (block_cg, block_cgls, cg_guarded,
                                    clear_fused_cache)
from pylops_mpi_tpu.solvers import ca
from pylops_mpi_tpu.solvers.basic import _cg_fused, _cgls_fused
from pylops_mpi_tpu.solvers.segmented import cg_segmented, cgls_segmented
from pylops_mpi_tpu.utils import deps, hlo

_strip = hlo.strip_provenance

_CA_KNOBS = ("PYLOPS_MPI_TPU_CA", "PYLOPS_MPI_TPU_CA_S",
             "PYLOPS_MPI_TPU_REDUCE_STALL")


@pytest.fixture(autouse=True)
def _fresh_ca_env():
    saved = {k: os.environ.get(k) for k in _CA_KNOBS}
    for k in _CA_KNOBS:
        os.environ.pop(k, None)
    PR.set_precision(None)
    rstatus.clear_statuses()
    ca.clear_fallback()
    clear_fused_cache()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    PR.set_precision(None)
    rstatus.clear_statuses()
    ca.clear_fallback()
    clear_fused_cache()


def _set_mode(mode, s=None):
    os.environ["PYLOPS_MPI_TPU_CA"] = mode
    if s is not None:
        os.environ["PYLOPS_MPI_TPU_CA_S"] = str(s)
    clear_fused_cache()


def _spd_problem(rng, nblk=8, nloc=8, dtype=np.float64, spread=1e2):
    import scipy.linalg as spla
    mats, scales = [], np.logspace(0, np.log10(spread), nblk)
    for s in scales:
        a = rng.standard_normal((nloc, nloc))
        mats.append((((a @ a.T) * 0.1 + nloc * np.eye(nloc)) * s)
                    .astype(dtype))
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    dense = spla.block_diag(*mats).astype(np.float64)
    xt = rng.standard_normal(nblk * nloc)
    y = DistributedArray.to_dist((dense @ xt).astype(dtype))
    return Op, dense, xt, y


def _ls_problem(rng, nblk=8, bm=10, bn=6, dtype=np.float64):
    import scipy.linalg as spla
    mats = [rng.standard_normal((bm, bn)).astype(dtype)
            for _ in range(nblk)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    dense = spla.block_diag(*mats).astype(np.float64)
    xt = rng.standard_normal(nblk * bn)
    yv = dense @ xt
    y = DistributedArray.to_dist(yv.astype(dtype))
    xs = np.linalg.lstsq(dense, yv, rcond=None)[0]
    return Op, dense, xs, y


def _zeros_like_cols(Op, dtype):
    return DistributedArray.to_dist(np.zeros(Op.shape[1], dtype=dtype))


# ------------------------------------------------ knob accessors
def test_ca_knob_accessors(monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_CA", raising=False)
    assert deps.ca_mode() == "off"
    for v in ("off", "pipelined", "sstep", "auto"):
        monkeypatch.setenv("PYLOPS_MPI_TPU_CA", v)
        assert deps.ca_mode() == v
    monkeypatch.setenv("PYLOPS_MPI_TPU_CA", "bogus")
    assert deps.ca_mode() == "off"  # malformed never breaks a solve
    monkeypatch.delenv("PYLOPS_MPI_TPU_CA_S", raising=False)
    assert deps.ca_s_default() >= 2
    monkeypatch.setenv("PYLOPS_MPI_TPU_CA_S", "6")
    assert deps.ca_s_default() == 6
    monkeypatch.setenv("PYLOPS_MPI_TPU_CA_S", "junk")
    assert deps.ca_s_default() >= 2
    monkeypatch.delenv("PYLOPS_MPI_TPU_REDUCE_STALL", raising=False)
    assert deps.reduce_stall_steps() == 0
    monkeypatch.setenv("PYLOPS_MPI_TPU_REDUCE_STALL", "128")
    assert deps.reduce_stall_steps() == 128
    monkeypatch.setenv("PYLOPS_MPI_TPU_REDUCE_STALL", "junk")
    assert deps.reduce_stall_steps() == 0


def test_reductions_per_iter_tables():
    assert ca.classic_reductions_per_iter("cg") == 2
    assert ca.classic_reductions_per_iter("cgls") == 5
    assert ca.ca_reductions_per_iter("pipelined") == 1
    assert ca.ca_reductions_per_iter("sstep", 4) == pytest.approx(0.25)


# ------------------------------------------------ CA=off bit-identity
def test_ca_off_hlo_bit_identical(rng):
    """The acceptance bar of the ``off`` leg: with the knob explicitly
    off (or the stall knob explicitly 0) the compiled classic program
    is byte-identical to the knob-unset program — the CA tier and the
    stall seam cost NOTHING when disabled."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)

    def f(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)

    base = hlo.compiled_hlo(f, y, x0, 0.0)
    for env in ({"PYLOPS_MPI_TPU_CA": "off"},
                {"PYLOPS_MPI_TPU_REDUCE_STALL": "0"},
                {"PYLOPS_MPI_TPU_CA": "off",
                 "PYLOPS_MPI_TPU_REDUCE_STALL": "0"}):
        for k, v in env.items():
            os.environ[k] = v
        clear_fused_cache()
        h = hlo.compiled_hlo(f, y, x0, 0.0)
        assert _strip(h) == _strip(base)
        for k in env:
            os.environ.pop(k)
    # ... and the pipelined program really is a different program
    def p(y_, x_, tol):
        return ca._pipe_cg_fused(Op, y_, x_, tol, niter=10)
    assert _strip(hlo.compiled_hlo(p, y, x0, 0.0)) \
        != _strip(base)


def test_stall_knob_changes_program_not_result(rng):
    """The injected latency chain perturbs the PROGRAM (it must
    survive the compiler) but never the RESULT (it folds back as
    ``+0``) — and the fused-cache key separates the two programs."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float64)
    x0 = _zeros_like_cols(Op, np.float64)
    x_a, it_a, _ = pmt.cg(Op, y, x0, niter=25, tol=0.0, fused=True)
    os.environ["PYLOPS_MPI_TPU_REDUCE_STALL"] = "64"
    clear_fused_cache()
    x_b, it_b, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float64),
                          niter=25, tol=0.0, fused=True)
    assert int(it_a) == int(it_b)
    np.testing.assert_array_equal(np.asarray(x_a.asarray()),
                                  np.asarray(x_b.asarray()))

    # distinct closures per compile: jax caches lowerings on the
    # callable's identity, so reusing one ``f`` across the env flip
    # would silently return the first program twice
    def f_on(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)
    h_on = hlo.compiled_hlo(f_on, y, _zeros_like_cols(Op, np.float64),
                            0.0)
    os.environ.pop("PYLOPS_MPI_TPU_REDUCE_STALL")
    clear_fused_cache()

    def f_off(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)
    h_off = hlo.compiled_hlo(f_off, y,
                             _zeros_like_cols(Op, np.float64), 0.0)
    assert _strip(h_on) != _strip(h_off)


# ------------------------------------------------ reduction-count pins
def test_pipelined_single_reduction_pinned(rng):
    """THE tentpole pin: classic CG pays ≥2 all-reduces per iteration
    body, the pipelined engine EXACTLY ONE — with and without a
    preconditioner — and pipelined CGLS merges its five."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)

    def classic(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)

    n_classic = hlo.count_reductions(
        hlo.compiled_hlo(classic, y, x0, 0.0), scope="body")
    assert n_classic >= 2

    def pipe(y_, x_, tol):
        return ca._pipe_cg_fused(Op, y_, x_, tol, niter=10)

    hlo.assert_single_reduction(pipe, y, x0, 0.0)

    M = JacobiPrecond.from_operator(Op)

    def pipe_m(y_, x_, tol):
        return ca._pipe_cg_fused(Op, y_, x_, tol, niter=10, M=M)

    hlo.assert_single_reduction(pipe_m, y, x0, 0.0)

    OpL, _, _, yL = _ls_problem(rng, dtype=np.float32)
    xL = _zeros_like_cols(OpL, np.float32)

    def ls_classic(y_, x_, damp, tol):
        return _cgls_fused(OpL, y_, x_, damp, tol, niter=10)

    assert hlo.count_reductions(
        hlo.compiled_hlo(ls_classic, yL, xL, 0.0, 0.0),
        scope="body") >= 2

    def ls_pipe(y_, x_, damp, tol):
        return ca._pipe_cgls_fused(OpL, y_, x_, damp, tol, niter=10)

    hlo.assert_single_reduction(ls_pipe, yL, xL, 0.0, 0.0)


def test_sstep_one_gram_reduction_per_outer(rng):
    """The s-step body performs ONE collective (the stacked Gram
    reduction) per s iterations, for every s in the tuning axis."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)
    for s in (2, 4, 8):
        def f(y_, x_, tol, _s=s):
            return ca._sstep_cg_fused(Op, y_, x_, tol, niter=16, s=_s)
        assert hlo.count_reductions(
            hlo.compiled_hlo(f, y, x0, 0.0), scope="body") == 1


# ------------------------------------------------ fixed-point parity
@pytest.mark.parametrize("mode", ["pipelined", "sstep"])
@pytest.mark.parametrize("use_m", [False, True])
def test_cg_matches_classic_fixed_point(rng, mode, use_m):
    Op, dense, xt, y = _spd_problem(rng)
    M = BlockJacobiPrecond.from_block_diag(Op) if use_m else None
    # realizable tolerance: below the f64 floor the pipelined
    # residual recurrence drifts and iteration counts decouple
    tol = 1e-12
    x_c, it_c, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float64),
                          niter=200, tol=tol, fused=True, M=M)
    _set_mode(mode)
    x_a, it_a, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float64),
                          niter=200, tol=tol, fused=True, M=M)
    err_c = np.linalg.norm(np.asarray(x_c.asarray()) - xt) \
        / np.linalg.norm(xt)
    err_a = np.linalg.norm(np.asarray(x_a.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert err_c < 1e-8 and err_a < 1e-8
    # iteration parity: ±10% + 1 (the pipelined stop test lags one)
    assert abs(int(it_a) - int(it_c)) <= \
        max(2, round(0.1 * int(it_c)) + 1)


@pytest.mark.parametrize("mode", ["pipelined", "sstep"])
def test_cgls_matches_classic_fixed_point(rng, mode):
    Op, dense, xs, y = _ls_problem(rng)
    x_c = pmt.cgls(Op, y, _zeros_like_cols(Op, np.float64), niter=200,
                   tol=1e-22, fused=True)
    _set_mode(mode)  # sstep CGLS routes to pipelined (documented)
    x_a = pmt.cgls(Op, y, _zeros_like_cols(Op, np.float64), niter=200,
                   tol=1e-22, fused=True)
    for x in (x_c[0], x_a[0]):
        err = np.linalg.norm(np.asarray(x.asarray()) - xs) \
            / np.linalg.norm(xs)
        assert err < 1e-7
    assert abs(int(x_a[2]) - int(x_c[2])) \
        <= max(1, round(0.1 * int(x_c[2])))


def test_cg_bf16_storage_parity(rng):
    """The CA engines obey the storage-precision seam: bf16 pipelined
    lands within bf16 distance of the classic bf16 solve."""
    PR.set_precision("bf16")
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32, spread=1.0)
    x_c, it_c, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float32),
                          niter=60, tol=0.0, fused=True)
    _set_mode("pipelined")
    x_p, it_p, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float32),
                          niter=60, tol=0.0, fused=True)
    a = np.asarray(x_c.asarray(), dtype=np.float64)
    b = np.asarray(x_p.asarray(), dtype=np.float64)
    assert np.linalg.norm(a - b) / np.linalg.norm(a) < 0.05


@pytest.mark.parametrize("engine", ["block_cg", "block_cgls"])
@pytest.mark.parametrize("mode", ["pipelined", "sstep"])
def test_block_matches_classic_fixed_point(rng, engine, mode):
    K = 3
    if engine == "block_cg":
        Op, dense, xt, _ = _spd_problem(rng, dtype=np.float32)
        run = block_cg
        kw = {}
    else:
        Op, dense, xt, _ = _ls_problem(rng, dtype=np.float32)
        run = block_cgls
        kw = {}
    N = Op.shape[0]
    Y = rng.standard_normal((N, K)).astype(np.float32)
    yb = DistributedArray(global_shape=(N, K), dtype=np.float32)
    yb[:] = Y
    out_c = run(Op, yb, niter=40, tol=0.0, **kw)
    _set_mode(mode)
    out_a = run(Op, yb, niter=40, tol=0.0, **kw)
    a = np.asarray(out_c[0].asarray(), dtype=np.float64)
    b = np.asarray(out_a[0].asarray(), dtype=np.float64)
    assert np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-30) < 1e-3


# ------------------------------------------------ guards compose
def test_poisoned_column_freeze_survives_pipelined(rng):
    """Per-column freeze under the pipelined engine: a NaN column
    breaks down ALONE; its siblings land on the clean block solve."""
    K = 4
    mats = []
    for _ in range(8):
        m = rng.standard_normal((12, 12)).astype(np.float32)
        mats.append(np.eye(12, dtype=np.float32) * 4
                    + 0.3 * (m + m.T))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    N = Op.shape[0]
    Y = rng.standard_normal((N, K)).astype(np.float32)
    yb = DistributedArray(global_shape=(N, K), dtype=np.float32)
    yb[:] = Y
    _set_mode("pipelined")
    x_clean, _, _ = block_cg(Op, yb, niter=80, tol=1e-6)
    Yp = Y.copy()
    Yp[0, 1] = np.nan
    yp = DistributedArray(global_shape=Y.shape, dtype=np.float32)
    yp[:] = Yp
    xp, _, _ = block_cg(Op, yp, niter=80, tol=1e-6, guards=True)
    info = rstatus.last_status("block_cg")
    assert info["columns"][1] == rstatus.BREAKDOWN
    for j in (0, 2, 3):
        assert info["columns"][j] == rstatus.CONVERGED
        np.testing.assert_allclose(np.asarray(xp.array)[:, j],
                                   np.asarray(x_clean.array)[:, j],
                                   rtol=0, atol=1e-5)


def test_guarded_pipelined_records_status(rng):
    Op, dense, xt, y = _spd_problem(rng)
    _set_mode("pipelined")
    x, it, cost, code = cg_guarded(Op, y, niter=200, tol=1e-18)
    assert code == rstatus.CONVERGED
    info = rstatus.last_status("cg")
    assert info["status"] == rstatus.CONVERGED
    err = np.linalg.norm(np.asarray(x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert err < 1e-8


# ------------------------------------------------ sstep guard rails
def test_sstep_breakdown_falls_back_to_pipelined(rng):
    """The monomial-basis conditioning guard: an ill-conditioned f32
    system at deep s breaks the local basis; the solve must NOT
    return garbage — it restarts mid-solve under the pipelined engine
    (recorded via ``ca.last_fallback``) and still converges."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32, spread=1e4)
    _set_mode("sstep", s=8)
    ca.clear_fallback()
    x, it, cost = pmt.cg(Op, y, _zeros_like_cols(Op, np.float32),
                         niter=300, tol=1e-10, fused=True,
                         guards=True)
    fb = ca.last_fallback()
    assert fb is not None and fb["solver"] == "cg" and fb["s"] == 8
    # the breakdown was HANDLED, not surfaced: whatever terminal word
    # the continuation earns (stagnation is legitimate — the pipelined
    # recurrence drifts at f32/high cond), it is not BREAKDOWN
    info = rstatus.last_status("cg")
    assert info["status"] != rstatus.BREAKDOWN
    err = np.linalg.norm(np.asarray(x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert np.isfinite(err) and err < 0.5  # real progress, not garbage
    # basis broke at iteration 0 here, so the continuation IS a pure
    # pipelined solve — pin it bit-for-bit
    _set_mode("pipelined")
    ca.clear_fallback()
    xp_, itp, _ = pmt.cg(Op, y, _zeros_like_cols(Op, np.float32),
                         niter=300, tol=1e-10, fused=True,
                         guards=True)
    assert ca.last_fallback() is None
    np.testing.assert_array_equal(np.asarray(x.asarray()),
                                  np.asarray(xp_.asarray()))


def test_sstep_ineligible_routes_to_pipelined(rng):
    """Complex dtype needs signed/conjugated Gram algebra the
    monomial-coordinate machinery does not carry — sstep silently
    routes those solves to the pipelined engine instead of corrupting
    them."""
    nblk, nloc = 4, 6
    mats = []
    for _ in range(nblk):
        a = (rng.standard_normal((nloc, nloc))
             + 1j * rng.standard_normal((nloc, nloc)))
        mats.append((a @ a.conj().T
                     + nloc * np.eye(nloc)).astype(np.complex128))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.complex128)
                       for m in mats])
    import scipy.linalg as spla
    dense = spla.block_diag(*mats)
    xt = rng.standard_normal(nblk * nloc) \
        + 1j * rng.standard_normal(nblk * nloc)
    y = DistributedArray.to_dist(dense @ xt)
    _set_mode("sstep")
    x, it, _ = pmt.cg(Op, y, niter=100, tol=1e-20, fused=True)
    err = np.linalg.norm(np.asarray(x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert err < 1e-8


# ------------------------------------------------ segmented compose
@pytest.mark.parametrize("mode", ["pipelined", "sstep"])
def test_segmented_kill_resume_identity_per_mode(rng, tmp_path, mode):
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)
    _set_mode(mode)
    ref = cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5)
    path = str(tmp_path / "carry.ckpt")

    class Kill(Exception):
        pass

    def killer(info):
        if info["epoch"] == 2:
            raise Kill

    with pytest.raises(Kill):
        cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                     checkpoint_path=path, on_epoch=killer)
    res = cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                       checkpoint_path=path)
    assert res.iiter == ref.iiter
    np.testing.assert_array_equal(np.asarray(res.x.asarray()),
                                  np.asarray(ref.x.asarray()))


def test_segmented_resume_refuses_mode_mismatch(rng, tmp_path):
    """A carry banked under one CA mode carries a different pytree —
    resuming it under another mode must refuse, not misread it."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)
    path = str(tmp_path / "carry.ckpt")
    _set_mode("pipelined")

    class Kill(Exception):
        pass

    def killer(info):
        if info["epoch"] == 1:
            raise Kill

    with pytest.raises(Kill):
        cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                     checkpoint_path=path, on_epoch=killer)
    _set_mode("off")
    with pytest.raises(ValueError, match="resume must replay"):
        cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                     checkpoint_path=path)
    _set_mode("sstep")
    with pytest.raises(ValueError, match="resume must replay"):
        cg_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                     checkpoint_path=path)


@pytest.mark.slow
def test_segmented_cgls_pipelined_matches_full(rng):
    Op, dense, xs, y = _ls_problem(rng, dtype=np.float32)
    x0 = _zeros_like_cols(Op, np.float32)
    _set_mode("pipelined")
    res = cgls_segmented(Op, y, x0, niter=60, tol=0.0, epoch=7)
    err = np.linalg.norm(np.asarray(res.x.asarray()) - xs) \
        / np.linalg.norm(xs)
    assert err < 1e-4


# ------------------------------------------------ mode resolution
def test_auto_mode_prefers_pipelined_under_stall(rng):
    """``auto`` weighs the α-term: with an armed latency injection the
    reduction cost is real and auto picks the pipelined engine; bare
    CPU-sim solves (no latency to avoid) stay classic."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    os.environ["PYLOPS_MPI_TPU_CA"] = "auto"
    clear_fused_cache()
    os.environ["PYLOPS_MPI_TPU_REDUCE_STALL"] = "256"
    assert ca.resolve_mode(Op, "cg") == "pipelined"
    os.environ.pop("PYLOPS_MPI_TPU_REDUCE_STALL")


def test_batched_solve_stays_classic(rng):
    """``batched_solve`` vmaps one compiled program over an operator
    family — it calls the classic builder directly and must keep
    doing so under a global CA knob (documented composition limit)."""
    from pylops_mpi_tpu.distributedarray import Partition
    from pylops_mpi_tpu.ops.fredholm import MPIFredholm1
    from pylops_mpi_tpu.solvers import batched_solve

    B, nsl, nx, ny, nz = 3, 8, 6, 6, 2

    def factory(G):
        return MPIFredholm1(G, nz=nz, dtype="float32")

    Gs = [(rng.standard_normal((nsl, nx, ny))
           + 3 * np.eye(nx, ny)).astype(np.float32) for _ in range(B)]
    N = nsl * nx * nz
    ys = []
    for _ in range(B):
        y = DistributedArray(global_shape=N,
                             partition=Partition.BROADCAST,
                             dtype=np.float32)
        y[:] = rng.standard_normal(N).astype(np.float32)
        ys.append(y)

    # classic oracle with CA off ...
    seq = [pmt.cgls(factory(G), y, niter=15, tol=0.0)[0]
           for G, y in zip(Gs, ys)]
    # ... must be what the batched path produces under a CA knob
    _set_mode("pipelined")
    res = batched_solve(factory, Gs, ys, solver="cgls", niter=15,
                        tol=0.0)
    assert len(res.xs) == B
    for b in range(B):
        np.testing.assert_allclose(np.asarray(res.xs[b].array),
                                   np.asarray(seq[b].array),
                                   rtol=0, atol=1e-4)
