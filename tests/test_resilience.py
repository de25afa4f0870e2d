"""Chaos suite for the resilient solver runtime (ISSUE 6): in-loop
guards (status word, breakdown/stagnation detection, HLO pins),
fault injection, precision-escalation restarts, segmented
checkpoint/resume, and bounded retry/backoff."""

import os

import numpy as np
import pytest

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag, resilience
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.resilience import faults, retry, status as rstatus
from pylops_mpi_tpu.solvers.basic import (cg_guarded, cgls_guarded,
                                          _cg_fused, _cgls_fused)
from pylops_mpi_tpu.solvers.segmented import cg_segmented, cgls_segmented
from pylops_mpi_tpu.solvers.sparsity import ista_guarded, fista_guarded
from pylops_mpi_tpu.utils import hlo


@pytest.fixture(autouse=True)
def _clean_chaos():
    """No armed fault or recorded status may leak between tests."""
    faults.disarm()
    rstatus.clear_statuses()
    yield
    faults.disarm()
    rstatus.clear_statuses()


def spd_problem(rng, nblk=8, n=6):
    mats = []
    for _ in range(nblk):
        a = rng.standard_normal((n, n))
        mats.append(a @ a.T + n * np.eye(n))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    dense = np.zeros((nblk * n, nblk * n))
    for i, m in enumerate(mats):
        dense[i * n:(i + 1) * n, i * n:(i + 1) * n] = m
    xtrue = rng.standard_normal(nblk * n)
    y = DistributedArray.to_dist(dense @ xtrue)
    x0 = DistributedArray.to_dist(np.zeros(nblk * n))
    return Op, dense, xtrue, y, x0


def ls_problem(rng, nblk=8, bm=7, bn=4):
    mats = [rng.standard_normal((bm, bn)) for _ in range(nblk)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float64) for m in mats])
    xtrue = rng.standard_normal(nblk * bn)
    y = np.concatenate([m @ xtrue[i * bn:(i + 1) * bn]
                        for i, m in enumerate(mats)])
    return Op, xtrue, DistributedArray.to_dist(y), \
        DistributedArray.to_dist(np.zeros(nblk * bn))


# ------------------------------------------------------- status word
def test_guarded_cg_converged(rng):
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    x, iiter, cost, code = cg_guarded(Op, y, x0, niter=200, tol=1e-12)
    assert code == rstatus.CONVERGED
    assert rstatus.status_name(code) == "converged"
    np.testing.assert_allclose(x.asarray(), xtrue, rtol=1e-6, atol=1e-8)
    assert cost.shape[0] == iiter + 1
    assert rstatus.last_status("cg")["status_name"] == "converged"


def test_guarded_cg_maxiter(rng):
    Op, _, _, y, x0 = spd_problem(rng)
    x, iiter, cost, code = cg_guarded(Op, y, x0, niter=3, tol=1e-30)
    assert code == rstatus.MAXITER and iiter == 3


def test_guarded_cgls_matches_unguarded(rng):
    """The guard carry must not perturb the trajectory: guarded and
    plain fused CGLS produce the same iterates on a healthy solve."""
    Op, xtrue, y, x0 = ls_problem(rng)
    ref = pmt.cgls(Op, y, x0, niter=30, tol=0.0, guards=False)
    xg, iiter, cost, cost1, kold, code = cgls_guarded(
        Op, y, x0, niter=30, tol=0.0)
    np.testing.assert_array_equal(np.asarray(ref[0].asarray()),
                                  np.asarray(xg.asarray()))
    assert iiter == ref[2]
    assert code in (rstatus.MAXITER, rstatus.CONVERGED)


def test_public_wrappers_honor_env_gate(rng, monkeypatch):
    """PYLOPS_MPI_TPU_GUARDS=on routes the public fused path through
    the guarded builder — same return signature, status published."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "on")
    rstatus.clear_statuses()
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    x, iiter, cost = pmt.cg(Op, y, x0, niter=200, tol=1e-12)
    np.testing.assert_allclose(x.asarray(), xtrue, rtol=1e-6, atol=1e-8)
    assert rstatus.last_status("cg")["status_name"] == "converged"
    out = pmt.cgls(Op, y, x0, niter=200, tol=1e-12)
    assert out[1] == 1  # istop: converged
    assert rstatus.last_status("cgls") is not None


def test_guards_mode_unknown_value_warns(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "sideways")
    monkeypatch.setattr(rstatus, "_warned_mode", False)
    with pytest.warns(UserWarning, match="PYLOPS_MPI_TPU_GUARDS"):
        assert rstatus.guards_mode() == "off"
    assert not rstatus.guards_enabled()
    assert rstatus.guards_enabled(True)  # explicit kwarg beats env


# -------------------------------------------------- fault injection
def test_nan_injection_cg_breakdown_within_two_iters(rng):
    Op, _, _, y, x0 = spd_problem(rng)
    faults.arm("nan", 5)
    x, iiter, cost, code = cg_guarded(Op, y, x0, niter=200, tol=1e-30)
    assert code == rstatus.BREAKDOWN
    assert iiter <= 7  # detected within <=2 iterations of injection
    assert np.all(np.isfinite(np.asarray(x.asarray())))  # last finite
    assert faults.armed() is None  # one-shot fault consumed


def test_nan_injection_cgls_breakdown(rng):
    Op, xtrue, y, x0 = ls_problem(rng)
    faults.arm("nan", 4)
    x, iiter, cost, cost1, kold, code = cgls_guarded(
        Op, y, x0, niter=200, tol=1e-30)
    assert code == rstatus.BREAKDOWN and iiter <= 6
    assert np.all(np.isfinite(np.asarray(x.asarray())))


def test_stall_injection_stagnation(rng, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARD_STALL", "5")
    Op, _, _, y, x0 = spd_problem(rng)
    faults.arm("stall", 3)
    x, iiter, cost, code = cg_guarded(Op, y, x0, niter=200, tol=1e-30)
    assert code == rstatus.STAGNATION
    assert iiter < 200  # exited the loop early
    assert np.all(np.isfinite(np.asarray(x.asarray())))


def test_nan_injection_ista_fista_breakdown(rng):
    Op, _, _, y, x0 = spd_problem(rng)
    for fn, name in ((ista_guarded, "ista"), (fista_guarded, "fista")):
        faults.arm("nan", 3)
        x, iiter, cost, code = fn(Op, y, x0, niter=50, eps=0.01,
                                  alpha=0.02, tol=0.0)
        assert code == rstatus.BREAKDOWN, name
        assert iiter <= 5, name
        assert np.all(np.isfinite(np.asarray(x.asarray()))), name
        assert rstatus.last_status(name)["status_name"] == "breakdown"


def test_fault_arm_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.arm("gamma-ray", 3)
    with pytest.raises(ValueError, match="iteration"):
        faults.arm("nan", -1)
    faults.arm("nan", 2, once=False)
    assert faults.consume() == {"kind": "nan", "iteration": 2,
                                "once": False}
    assert faults.armed() is not None  # once=False survives consume
    faults.disarm()
    assert faults.fault_signature() == ("faults", None)


# ---------------------------------------------------------- HLO pins
def test_guards_off_bit_identical_and_no_guard_ops(rng, monkeypatch):
    """Guards off traces the exact pre-guard program: the default
    builder call and an explicit guards=False call lower to the same
    HLO, and neither contains a single finiteness-check op."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "off")
    Op, xtrue, y, x0 = ls_problem(rng)

    def f_default(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=15)

    def f_off(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=15, guards=False)

    h_default = hlo.compiled_hlo(f_default, y, x0, 0.0, 0.0)
    h_off = hlo.compiled_hlo(f_off, y, x0, 0.0, 0.0)
    assert hlo.strip_provenance(h_default) == hlo.strip_provenance(h_off)
    assert "is-finite" not in h_default


def test_guards_on_zero_host_callbacks_and_traced_guards(rng):
    """Guards on: the status word is computed entirely on device (zero
    host callbacks — the ISSUE 6 acceptance pin) and the finiteness
    checks ARE in the program."""
    Op, xtrue, y, x0 = ls_problem(rng)

    def f_on(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=15, guards=True,
                           stall_n=50)

    h_on = hlo.assert_no_host_callbacks(f_on, y, x0, 0.0, 0.0)
    assert "is-finite" in h_on


def test_guarded_cache_key_no_cross_mode_reuse(rng, monkeypatch):
    """Flipping the guard gate must retrace, never reuse an executable
    compiled under the other mode (fused cache keyed on guards)."""
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "off")
    x_off, it_off, _ = pmt.cg(Op, y, x0, niter=50, tol=1e-12)
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "on")
    rstatus.clear_statuses()
    x_on, it_on, _ = pmt.cg(Op, y, x0, niter=50, tol=1e-12)
    assert rstatus.last_status("cg") is not None  # guarded build ran
    assert it_on == it_off
    np.testing.assert_array_equal(np.asarray(x_off.asarray()),
                                  np.asarray(x_on.asarray()))


# --------------------------------------------- resilient_solve driver
def test_escalate_dtype_ladder():
    from pylops_mpi_tpu.ops._precision import escalate_dtype
    import jax.numpy as jnp
    assert escalate_dtype(jnp.bfloat16) == np.dtype(np.float32)
    assert escalate_dtype(np.float32) == np.dtype(np.float64)  # x64 on
    assert escalate_dtype(np.float64) is None
    assert escalate_dtype(np.complex64) == np.dtype(np.complex128)
    assert escalate_dtype(np.complex128) is None


def test_resilient_solve_bf16_breakdown_escalates_to_f32(rng):
    """The acceptance scenario: NaN injected at iteration k under the
    bf16 storage policy -> the guarded fused CGLS exits with
    status=breakdown within <=2 iterations, resilient_solve restarts
    one rung wider (f32) from the last finite iterate and matches the
    f64 oracle."""
    from pylops_mpi_tpu.ops import _precision
    mats = []
    for _ in range(8):
        a = rng.standard_normal((6, 6)).astype(np.float32)
        mats.append(a @ a.T + 6 * np.eye(6, dtype=np.float32))
    dense = np.zeros((48, 48))
    for i, m in enumerate(mats):
        dense[i * 6:(i + 1) * 6, i * 6:(i + 1) * 6] = m
    xtrue = rng.standard_normal(48)
    y32 = (dense @ xtrue).astype(np.float32)
    dy = DistributedArray.to_dist(y32)
    oracle = np.linalg.solve(dense, dense @ xtrue)

    _precision.set_precision("bf16")
    try:
        def make_op(cdt):
            return MPIBlockDiag(
                [MatrixMult(m, dtype=np.float32) for m in mats],
                compute_dtype=cdt)

        faults.arm("nan", 4)
        res = resilience.resilient_solve(make_op, dy, solver="cgls",
                                         niter=400, tol=1e-12)
    finally:
        _precision.set_precision(None)
    assert res.restarts == 1
    assert res.attempts[0]["compute_dtype"] == "bfloat16"
    assert res.attempts[0]["status"] == "breakdown"
    assert res.attempts[0]["iiter"] <= 6
    assert res.attempts[1]["compute_dtype"] == "float32"
    assert res.status in ("converged", "maxiter")
    err = (np.linalg.norm(np.asarray(res.x.asarray(), np.float64)
                          - oracle) / np.linalg.norm(oracle))
    assert err < 2e-3


def test_resilient_solve_bounded_restarts(rng):
    """max_restarts=0: the driver stops after the first breakdown
    instead of looping."""
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    faults.arm("nan", 3)
    res = resilience.resilient_solve(lambda cdt: Op, y, solver="cg",
                                     niter=100, tol=1e-12,
                                     max_restarts=0)
    assert res.status == "breakdown" and res.restarts == 0
    assert len(res.attempts) == 1


def test_resilient_solve_plain_operator_no_escalation(rng):
    """A plain operator (no factory) disables escalation; a healthy
    solve still converges through the driver."""
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    res = resilience.resilient_solve(Op, y, solver="cg", niter=200,
                                     tol=1e-12)
    assert res.status == "converged" and res.restarts == 0
    np.testing.assert_allclose(res.x.asarray(), xtrue, rtol=1e-6,
                               atol=1e-8)


def test_resilient_solve_rejects_unknown_solver(rng):
    Op, _, _, y, x0 = spd_problem(rng)
    with pytest.raises(ValueError, match="solver="):
        resilience.resilient_solve(Op, y, solver="gmres")


# ------------------------------------------- segmented fused solves
def test_segmented_single_epoch_equals_fused(rng):
    Op, xtrue, y, x0 = ls_problem(rng)
    ref = pmt.cgls(Op, y, x0, niter=30, tol=0.0)
    seg = cgls_segmented(Op, y, x0, niter=30, tol=0.0, epoch=30)
    np.testing.assert_array_equal(np.asarray(ref[0].asarray()),
                                  np.asarray(seg.x.asarray()))
    assert seg.iiter == ref[2] and seg.epochs == 1


def test_segmented_cg_matches_fused(rng):
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    ref = pmt.cg(Op, y, x0, niter=60, tol=1e-12)
    seg = cg_segmented(Op, y, x0, niter=60, tol=1e-12, epoch=7)
    assert seg.iiter == ref[1] and seg.status == "converged"
    np.testing.assert_allclose(np.asarray(seg.x.asarray()),
                               np.asarray(ref[0].asarray()),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("backend", ["native", "orbax"])
def test_segmented_kill_resume_trajectory_identity(rng, tmp_path,
                                                   backend):
    """Kill a segmented fused CGLS between epochs; resuming from the
    checkpoint yields the SAME final iterate (exact equality) and
    iteration count as the uninterrupted run — the ISSUE 6 acceptance
    bar — under both checkpoint backends."""
    if backend == "orbax":
        pytest.importorskip("orbax.checkpoint")
    Op, xtrue, y, x0 = ls_problem(rng)
    ref = cgls_segmented(Op, y, x0, niter=40, tol=0.0, epoch=5)

    path = str(tmp_path / "carry.ckpt")

    class Kill(Exception):
        pass

    def killer(info):
        if info["epoch"] == 3:
            raise Kill

    with pytest.raises(Kill):
        cgls_segmented(Op, y, x0, niter=40, tol=0.0, epoch=5,
                       checkpoint_path=path, backend=backend,
                       on_epoch=killer)
    assert os.path.exists(path)
    res = cgls_segmented(Op, y, x0, niter=40, tol=0.0, epoch=5,
                         checkpoint_path=path, backend=backend)
    assert res.iiter == ref.iiter == 40
    assert res.epochs == 5  # resumed: only the remaining epochs ran
    np.testing.assert_array_equal(np.asarray(res.x.asarray()),
                                  np.asarray(ref.x.asarray()))
    np.testing.assert_array_equal(res.cost, ref.cost)


def test_segmented_resume_plan_mismatch_raises(rng, tmp_path):
    Op, xtrue, y, x0 = ls_problem(rng)
    path = str(tmp_path / "c.ckpt")
    cgls_segmented(Op, y, x0, niter=20, tol=0.0, epoch=5,
                   checkpoint_path=path)
    with pytest.raises(ValueError, match="resume must replay"):
        cgls_segmented(Op, y, x0, niter=25, tol=0.0, epoch=5,
                       checkpoint_path=path)


def test_segmented_guarded_status(rng):
    Op, dense, xtrue, y, x0 = spd_problem(rng)
    seg = cg_segmented(Op, y, x0, niter=100, tol=1e-12, epoch=9,
                       guards=True)
    assert seg.status == "converged"
    assert rstatus.last_status("cg")["status_name"] == "converged"


def test_segmented_epoch_env_default(rng, monkeypatch):
    from pylops_mpi_tpu.solvers.segmented import resolve_epoch
    monkeypatch.delenv("PYLOPS_MPI_TPU_SEGMENT", raising=False)
    assert resolve_epoch(None, 40) == 40
    monkeypatch.setenv("PYLOPS_MPI_TPU_SEGMENT", "8")
    assert resolve_epoch(None, 40) == 8
    assert resolve_epoch(13, 40) == 13   # explicit kwarg beats env
    assert resolve_epoch(999, 40) == 40  # clamped to niter


# ------------------------------------------------- fused-carry schema
def test_fused_carry_schema_validation(rng, tmp_path):
    from pylops_mpi_tpu.utils import checkpoint as ckpt
    p = str(tmp_path / "f.ckpt")
    ckpt.save_fused_carry(p, "cgls", {"niter": 3, "kold": 1.0})
    with pytest.raises(ValueError, match="is for 'cgls'"):
        ckpt.load_fused_carry(p, "cg")
    out = ckpt.load_fused_carry(p, "cgls")
    assert out["niter"] == 3
    # a class-API snapshot is not a fused carry
    ckpt.save_pytree(p, {"niter": 3})
    with pytest.raises(ValueError, match="not a fused-carry"):
        ckpt.load_fused_carry(p, "cgls")


def test_native_backend_refuses_non_addressable_shards(tmp_path):
    """Satellite: the native backend names the orbax fix instead of
    failing deep inside a cross-host gather."""
    from pylops_mpi_tpu.utils import checkpoint as ckpt
    d = DistributedArray.to_dist(np.arange(8.0))

    class _NonAddressable:
        is_fully_addressable = False

    d._arr = _NonAddressable()
    with pytest.raises(RuntimeError, match="orbax"):
        ckpt.save_pytree(str(tmp_path / "x.ckpt"), {"x": d})


# -------------------------------------------------- retry / backoff
def test_retry_call_bounded_recovery():
    calls = []
    fn = faults.flaky(lambda v: v * 2, failures=2)
    out = retry.retry_call(fn, 21, retries=3, backoff_s=0.0,
                           sleep=lambda s: calls.append(s))
    assert out == 42 and fn.calls == 3
    assert len(calls) == 0  # backoff_s=0: no sleeps requested


def test_retry_call_exhausted_reraises():
    fn = faults.flaky(lambda: "ok", failures=5)
    with pytest.raises(TimeoutError, match="injected"):
        retry.retry_call(fn, retries=2, backoff_s=0.0)
    assert fn.calls == 3  # 1 attempt + 2 retries, bounded


def test_retry_backoff_doubles_and_caps():
    slept = []
    fn = faults.flaky(lambda: "ok", failures=3)
    retry.retry_call(fn, retries=3, backoff_s=1.0, sleep=slept.append)
    assert slept == [1.0, 2.0, 4.0]


def test_retry_jitter_default_off_is_exact(monkeypatch):
    """ISSUE 8 satellite pin: with the knob unset, backoff stays the
    exact doubling schedule — jitter is strictly opt-in."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_RETRY_JITTER", raising=False)
    assert retry.default_jitter() == 0.0
    slept = []
    fn = faults.flaky(lambda: "ok", failures=3)
    retry.retry_call(fn, retries=3, backoff_s=1.0, sleep=slept.append)
    assert slept == [1.0, 2.0, 4.0]


def test_retry_jitter_decorrelates_within_bounds():
    """Each jittered sleep lands in [(1-j)·wait, wait] — shrink-only,
    cap unchanged — and an injected rng makes it deterministic."""
    import random as _random
    slept = []
    fn = faults.flaky(lambda: "ok", failures=3)
    retry.retry_call(fn, retries=3, backoff_s=1.0, jitter=0.25,
                     rng=_random.Random(0), sleep=slept.append)
    base = [1.0, 2.0, 4.0]
    assert len(slept) == 3 and slept != base
    for got, want in zip(slept, base):
        assert 0.75 * want <= got <= want
    # same seed → same schedule (reproducible chaos runs)
    again = []
    fn2 = faults.flaky(lambda: "ok", failures=3)
    retry.retry_call(fn2, retries=3, backoff_s=1.0, jitter=0.25,
                     rng=_random.Random(0), sleep=again.append)
    assert again == slept


def test_retry_jitter_env_knob(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_RETRY_JITTER", "0.5")
    assert retry.default_jitter() == 0.5
    monkeypatch.setenv("PYLOPS_MPI_TPU_RETRY_JITTER", "7")
    assert retry.default_jitter() == 1.0  # clamped
    monkeypatch.setenv("PYLOPS_MPI_TPU_RETRY_JITTER", "nope")
    assert retry.default_jitter() == 0.0  # unparseable → off


def test_retry_if_vetoes_non_retryable():
    """The predicate sees the exception; False re-raises unchanged on
    the FIRST failure — an auth error is not a flaky coordinator."""
    fn = faults.flaky(lambda: "ok", failures=2)
    with pytest.raises(TimeoutError, match="injected"):
        retry.retry_call(fn, retries=5, backoff_s=0.0,
                         retry_if=lambda e: "transient" in str(e))
    assert fn.calls == 1  # vetoed immediately, no retry burned

    fn2 = faults.flaky(lambda: "ok", failures=2)
    out = retry.retry_call(fn2, retries=5, backoff_s=0.0,
                           retry_if=lambda e: isinstance(e, TimeoutError))
    assert out == "ok" and fn2.calls == 3


def test_initialize_multihost_retries_flaky_coordinator(monkeypatch):
    """The simulated coordinator timeout: jax.distributed.initialize
    fails twice, the bounded retry absorbs it."""
    import jax.distributed
    seen = {"n": 0}

    def fake_init(**kwargs):
        seen["n"] += 1
        if seen["n"] <= 2:
            raise TimeoutError("coordinator not listening")
        seen["kwargs"] = kwargs

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    pmt.initialize_multihost(coordinator_address="host:1234",
                             num_processes=2, process_id=0,
                             retries=3, backoff_s=0.0)
    assert seen["n"] == 3
    assert seen["kwargs"]["coordinator_address"] == "host:1234"
    # exhausted retries propagate the real error
    seen["n"] = -10
    monkeypatch.setattr(jax.distributed, "initialize",
                        faults.flaky(lambda **kw: None, failures=99))
    with pytest.raises(TimeoutError):
        pmt.initialize_multihost(retries=1, backoff_s=0.0)


# ------------------------------------------------ plan-cache chaos
@pytest.mark.parametrize("mode", ["truncate", "garbage", "schema"])
def test_plan_cache_corruption_degrades_to_miss(tmp_path, mode):
    from pylops_mpi_tpu.tuning import cache
    path = str(tmp_path / "plans.json")
    cache.clear_memory()
    cache.store("k1", {"params": {"schedule": "ring"},
                       "provenance": "tuned"}, path=path)
    assert cache.load_plans(path)["k1"]["provenance"] == "tuned"
    faults.corrupt_plan_cache(path, mode=mode)
    cache.clear_memory()
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert cache.load_plans(path) == {}      # logged miss, no raise
        assert cache.lookup("k1", path=path) is None
        # store() heals the damaged file
        cache.store("k2", {"params": {}}, path=path)
        assert cache.load_plans(path)["k2"] == {"params": {}}
    cache.clear_memory()


# ------------------------------------------------- iterative refinement
def _refine_problem(rng, n=48):
    """Moderately conditioned SPD system with a known f64 solution."""
    from pylops_mpi_tpu.ops.matrixmult import MPIMatrixMult
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    A64 = (q * np.linspace(1.0, 50.0, n)) @ q.T

    def make_op(dt):
        dt = np.dtype(dt or np.float64)
        return MPIMatrixMult(A64.astype(dt), 1, dtype=dt, kind="block")

    xt = rng.standard_normal(n)
    y = DistributedArray.to_dist(A64 @ xt)
    return A64, make_op, xt, y


def test_refined_solve_bf16_inner_reaches_f64_accuracy(rng, monkeypatch):
    """The refinement acceptance bar: bfloat16 inner solves, wide f64
    residual/correction, final error <= 1e-10 with >= 80% of matvecs
    narrow — and no attempt ever escalated off bfloat16. The
    no-escalation clause is a CLASSIC-engine pin (the pipelined
    recurrence drifts further in bf16 and legitimately escalates one
    attempt), so the CA knob is forced off here; CA × bf16 parity is
    covered by tests/test_ca.py."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_CA", "off")
    import jax.numpy as jnp
    A64, make_op, xt, y = _refine_problem(rng)
    res = resilience.refined_solve(
        make_op, y, solver="cg", niter=400, tol=1e-12,
        inner_dtype=jnp.bfloat16, inner_niter=60, inner_tol=1e-2,
        max_passes=12)
    err = np.linalg.norm(np.asarray(res.x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert res.status == "converged"
    assert err <= 1e-10
    assert res.narrow_frac >= 0.80
    assert all(a["compute_dtype"] == "bfloat16" for a in res.attempts)
    assert res.residuals[-1] < res.residuals[0]


def test_refined_solve_f32_inner(rng):
    import jax.numpy as jnp
    A64, make_op, xt, y = _refine_problem(rng)
    res = resilience.refined_solve(
        make_op, y, solver="cg", niter=400, tol=1e-12,
        inner_dtype=jnp.float32, inner_niter=80, inner_tol=1e-5,
        max_passes=8)
    err = np.linalg.norm(np.asarray(res.x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert res.status == "converged" and err <= 1e-10


def test_refined_solve_damped_cgls_fixed_point(rng):
    """damp > 0: refinement must land on the DAMPED normal-equations
    solution (AᵀA + damp²I)x = Aᵀy, not the undamped one."""
    import jax.numpy as jnp
    from pylops_mpi_tpu.ops.matrixmult import MPIMatrixMult
    n, m, damp = 40, 24, 0.7
    A64 = rng.standard_normal((n, m))

    def make_op(dt):
        dt = np.dtype(dt or np.float64)
        return MPIMatrixMult(A64.astype(dt), 1, dtype=dt, kind="block")

    xt = rng.standard_normal(m)
    yv = A64 @ xt
    y = DistributedArray.to_dist(yv)
    res = resilience.refined_solve(
        make_op, y, solver="cgls", niter=200, tol=1e-11, damp=damp,
        inner_dtype=jnp.float32, inner_niter=80, inner_tol=1e-4,
        max_passes=10)
    want = np.linalg.solve(A64.T @ A64 + damp ** 2 * np.eye(m),
                           A64.T @ yv)
    np.testing.assert_allclose(np.asarray(res.x.asarray()), want,
                               atol=1e-9)
    assert res.status == "converged"


def test_refined_solve_block_jacobi_fewer_inner_iters(rng):
    """The ``M=`` seam through ``refined_solve``'s inner solves: on a
    block-scaled ill-conditioned SPD system the block-Jacobi-
    preconditioned refinement reaches the same f64 accuracy with
    strictly fewer TOTAL inner iterations than the bare run — the
    preconditioner really reaches the correction solves, it is not
    dropped at the refinement boundary."""
    import jax.numpy as jnp
    from pylops_mpi_tpu.ops.precond import BlockJacobiPrecond
    nblk, nloc = 8, 8
    scales = np.logspace(0, 3, nblk)
    base = []
    for s in scales:
        a = rng.standard_normal((nloc, nloc))
        base.append(((a @ a.T) * 0.1 + nloc * np.eye(nloc)) * s)

    def make_op(dt):
        dt = np.dtype(dt or np.float64)
        return MPIBlockDiag([MatrixMult(b.astype(dt), dtype=dt)
                             for b in base])

    import scipy.linalg as spla
    dense = spla.block_diag(*base)
    xt = rng.standard_normal(nblk * nloc)
    y = DistributedArray.to_dist(dense @ xt)
    kw = dict(solver="cg", niter=400, tol=1e-10,
              inner_dtype=jnp.float32, inner_niter=120,
              inner_tol=1e-3, max_passes=12)
    bare = resilience.refined_solve(make_op, y, **kw)
    M = BlockJacobiPrecond.from_block_diag(make_op(np.float32))
    prec = resilience.refined_solve(make_op, y, M=M, **kw)
    for res in (bare, prec):
        err = np.linalg.norm(np.asarray(res.x.asarray()) - xt) \
            / np.linalg.norm(xt)
        assert res.status == "converged" and err <= 1e-8
    assert prec.iiter < bare.iiter


def test_refine_knob_routes_resilient_solve(rng, monkeypatch):
    """PYLOPS_MPI_TPU_REFINE=1 flips resilient_solve with a factory
    into refinement mode; the adapter surfaces a ResilientResult."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_REFINE", "1")
    A64, make_op, xt, y = _refine_problem(rng)
    res = resilience.resilient_solve(
        make_op, y, solver="cg", niter=400, tol=1e-11,
        inner_niter=80, inner_tol=1e-4)
    assert isinstance(res, resilience.ResilientResult)
    err = np.linalg.norm(np.asarray(res.x.asarray()) - xt) \
        / np.linalg.norm(xt)
    assert res.status == "converged" and err <= 1e-9


def test_refine_off_by_default(rng, monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_REFINE", raising=False)
    from pylops_mpi_tpu.utils.deps import refine_enabled
    assert not refine_enabled()
