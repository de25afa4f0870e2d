"""Differentiable operator layer (pylops_mpi_tpu/autodiff/).

Acceptance pins of the autodiff PR: adjoint VJP/JVP rules on operator
applies finite-difference check across engines × precisions (vector AND
parameter cotangents); the implicit fixed-point gradient through the
fused CG/CGLS matches the unrolled scan-tape oracle to ≤1e-5 in f64;
``PYLOPS_MPI_TPU_AUTODIFF=off`` lowers BYTE-identical solver programs
(the knob's host-side read is the tier's entire off-mode cost); the
``on``-mode reroute lets the classic entries run under ``jax.jit`` /
``jax.grad`` with host-contract-shaped traced returns.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.autodiff import (
    DifferentiableOperator, make_differentiable, cg_solve, cgls_solve,
    block_cg_solve, block_cgls_solve, unrolled_cg, unrolled_cgls, fit,
    trainable_leaves, param_count)
from pylops_mpi_tpu.autodiff import implicit as ad_implicit
from pylops_mpi_tpu.autodiff import rules as ad_rules
from pylops_mpi_tpu.solvers import clear_fused_cache
from pylops_mpi_tpu.solvers.basic import _cg_fused, _cgls_fused
from pylops_mpi_tpu.utils import deps, hlo

_strip = hlo.strip_provenance


@pytest.fixture(autouse=True)
def _fresh_autodiff_env():
    saved = os.environ.get("PYLOPS_MPI_TPU_AUTODIFF")
    os.environ.pop("PYLOPS_MPI_TPU_AUTODIFF", None)
    clear_fused_cache()
    yield
    if saved is None:
        os.environ.pop("PYLOPS_MPI_TPU_AUTODIFF", None)
    else:
        os.environ["PYLOPS_MPI_TPU_AUTODIFF"] = saved
    clear_fused_cache()


def _spd_problem(rng, nblk=8, nloc=6, dtype=np.float64):
    import scipy.linalg as spla
    mats = []
    for _ in range(nblk):
        a = rng.standard_normal((nloc, nloc))
        mats.append(((a @ a.T) * 0.1 + nloc * np.eye(nloc))
                    .astype(dtype))
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    dense = spla.block_diag(*mats).astype(np.float64)
    xt = rng.standard_normal(nblk * nloc)
    y = DistributedArray.to_dist((dense @ xt).astype(dtype))
    return Op, dense, xt, y


def _ls_problem(rng, nblk=8, bm=8, bn=5, dtype=np.float64):
    import scipy.linalg as spla
    mats = [rng.standard_normal((bm, bn)).astype(dtype)
            for _ in range(nblk)]
    Op = MPIBlockDiag([MatrixMult(m, dtype=dtype) for m in mats])
    dense = spla.block_diag(*mats).astype(np.float64)
    yv = dense @ rng.standard_normal(nblk * bn)
    y = DistributedArray.to_dist(yv.astype(dtype))
    return Op, dense, y


def _zeros(Op, dtype, side=1):
    return DistributedArray.to_dist(
        np.zeros(Op.shape[side], dtype=dtype))


def _fd_scalar(f, v, h=1e-5):
    """Central finite difference of scalar ``f`` along a random
    direction in the DistributedArray argument ``v``."""
    rng = np.random.default_rng(0)
    d = rng.standard_normal(v.global_shape[0]).astype(
        np.dtype(v.dtype))
    vp = DistributedArray.to_dist(v.asarray() + h * d,
                                  local_shapes=v.local_shapes)
    vm = DistributedArray.to_dist(v.asarray() - h * d,
                                  local_shapes=v.local_shapes)
    return (float(f(vp)) - float(f(vm))) / (2 * h), d


# ------------------------------------------------ knob accessors
def test_autodiff_knob_accessors(monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_AUTODIFF", raising=False)
    assert deps.autodiff_mode() == "off"
    assert not deps.autodiff_enabled()
    for v, want in (("on", "on"), ("1", "on"), ("true", "on"),
                    ("off", "off"), ("0", "off"), ("", "off")):
        monkeypatch.setenv("PYLOPS_MPI_TPU_AUTODIFF", v)
        assert deps.autodiff_mode() == want
    monkeypatch.setenv("PYLOPS_MPI_TPU_AUTODIFF", "bogus")
    assert deps.autodiff_mode() == "off"   # malformed never reroutes
    assert any(k[0] == "PYLOPS_MPI_TPU_AUTODIFF" for k in deps.KNOBS)


# ------------------------------------------------ operator VJP rules
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-2),
                                       (np.float64, 1e-6)])
@pytest.mark.parametrize("direction", ["matvec", "rmatvec"])
def test_vjp_rule_vector_fd(rng, dtype, tol, direction):
    """grad of ⟨w, A x⟩ w.r.t. x through the custom rule equals the
    finite difference, both applies, both precisions."""
    Op, dense, _, _ = _spd_problem(rng, dtype=dtype)
    D = make_differentiable(Op)
    assert isinstance(D, DifferentiableOperator)
    w = jnp.asarray(rng.standard_normal(Op.shape[0]).astype(dtype))
    x = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(dtype))

    def f(v):
        out = (D.matvec(v) if direction == "matvec"
               else D.rmatvec(v))
        return jnp.vdot(w, out._arr.ravel()).real

    g = jax.grad(f)(x)
    fd, d = _fd_scalar(f, x, h=1e-3 if dtype == np.float32 else 1e-6)
    got = float(np.vdot(g.asarray(), d))
    assert got == pytest.approx(fd, rel=tol, abs=tol)
    # analytic check: ∇ₓ⟨w, Ax⟩ = Aᵀw
    A = dense if direction == "matvec" else dense.T
    assert np.allclose(g.asarray(), A.T @ np.asarray(w),
                       rtol=10 * tol, atol=10 * tol)


def test_vjp_rule_param_cotangent_fd(rng):
    """grad w.r.t. the OPERATOR's own leaves (the BlockDiag's stacked
    block tensor) finite-difference checks — the pytree registration
    is the parameter seam."""
    Op, _, _, _ = _spd_problem(rng)
    x = DistributedArray.to_dist(rng.standard_normal(Op.shape[1]))
    w = jnp.asarray(rng.standard_normal(Op.shape[0]))

    def f(op):
        return jnp.vdot(w, op.matvec(x)._arr.ravel()).real

    D = make_differentiable(Op, params=True)
    g = jax.grad(f)(D)
    (gleaf,), _ = jax.tree_util.tree_flatten(g)
    leaf = jax.tree_util.tree_leaves(Op)[0]
    assert gleaf.shape == leaf.shape
    idx = (1, 2, 3)[:leaf.ndim]
    h = 1e-6
    for s in (+1, -1):
        pert = np.asarray(leaf).copy()
        pert[idx] += s * h
        Dp = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(D),
            [jnp.asarray(pert)])
        if s > 0:
            fp = float(f(Dp))
        else:
            fm = float(f(Dp))
    assert float(gleaf[idx]) == pytest.approx((fp - fm) / (2 * h),
                                              rel=1e-5, abs=1e-8)


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-2),
                                       (np.float64, 1e-6)])
def test_jvp_rule_fd(rng, dtype, tol):
    """mode='jvp': forward-mode tangent of A x is A dx (linearity)."""
    Op, dense, _, _ = _spd_problem(rng, dtype=dtype)
    D = make_differentiable(Op, mode="jvp")
    x = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(dtype))
    dx = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(dtype))
    y, dy = jax.jvp(lambda v: D.matvec(v), (x,), (dx,))
    assert np.allclose(np.asarray(dy.asarray(), dtype=np.float64),
                       dense @ dx.asarray(), rtol=tol, atol=tol)
    # rmatvec tangent too
    _, dz = jax.jvp(lambda v: D.rmatvec(v), (x,), (dx,))
    assert np.allclose(np.asarray(dz.asarray(), dtype=np.float64),
                       dense.T @ dx.asarray(), rtol=tol, atol=tol)


def test_sparse_param_cotangent(rng):
    """Sparse COO values get real cotangents; the integer structure
    (rows/cols) gets float0 — the pattern is not trainable."""
    from pylops_mpi_tpu.ops.sparse import MPISparseMatrixMult
    n = 16
    dense = np.zeros((n, n))
    ij = rng.integers(0, n, size=(40, 2))
    dense[ij[:, 0], ij[:, 1]] = rng.standard_normal(len(ij))
    Op = MPISparseMatrixMult.from_dense(dense)
    x = DistributedArray.to_dist(rng.standard_normal(n))
    w = np.asarray(rng.standard_normal(n))
    gop = ad_rules.param_cotangent(Op, x, DistributedArray.to_dist(w))
    leaves = jax.tree_util.tree_leaves(gop)
    f0 = [l for l in leaves
          if getattr(l, "dtype", None) == jax.dtypes.float0]
    real = [l for l in leaves
            if getattr(l, "dtype", None) != jax.dtypes.float0]
    assert len(f0) >= 1 and len(real) >= 1
    # ∂⟨w, A x⟩/∂data[k] = w[row_k] * x[col_k]
    rows = np.asarray(Op._rows)
    cols = np.asarray(Op._cols)
    data_ct = np.asarray(real[0]).ravel()
    want = np.asarray(w)[rows.ravel()] * x.asarray()[cols.ravel()]
    mask = np.asarray(Op._data).ravel() != 0  # padding slots
    assert np.allclose(data_ct[mask], want[mask], rtol=1e-10,
                       atol=1e-10)


def test_differentiable_operator_contract(rng):
    Op, _, _, _ = _spd_problem(rng)
    D = make_differentiable(Op)
    assert make_differentiable(D).args[0] is Op     # idempotent
    assert D.shape == Op.shape and D.dtype == Op.dtype
    assert D.H.shape == (Op.shape[1], Op.shape[0])
    with pytest.raises(ValueError, match="vjp.*jvp|jvp.*vjp"):
        make_differentiable(Op, mode="fwd")

    from pylops_mpi_tpu.linearoperator import MPILinearOperator

    class _Unreg(MPILinearOperator):   # subclass NOT pytree-registered
        pass

    unreg = _Unreg(shape=Op.shape, dtype=Op.dtype)
    with pytest.raises(ValueError, match="register_operator_arrays"):
        make_differentiable(unreg, params=True)
    # params=None auto-resolves to vector-only (closure form) instead
    assert make_differentiable(unreg)._params is False


# ------------------------------------- implicit vs unrolled oracle
def test_unrolled_matches_fused_forward(rng):
    """The scan-tape oracles land on the fused solvers' iterates —
    otherwise their gradients pin nothing."""
    Op, dense, xt, y = _spd_problem(rng)
    x0 = _zeros(Op, np.float64)
    xf, *_ = pmt.cg(Op, y, x0, niter=25, tol=0.0, fused=True)
    xu = unrolled_cg(Op, y, x0, niter=25)
    assert np.allclose(xu.asarray(), xf.asarray(), rtol=1e-10,
                       atol=1e-10)
    OpL, _, yL = _ls_problem(rng)
    x0L = _zeros(OpL, np.float64)
    # early (the recurrences still agree to rounding) and converged.
    # In between — around the 40-unknown system's finite-termination
    # point — the Krylov iterates amplify last-ulp differences in
    # reduction order between scan and while_loop (measured on jax
    # 0.9.0: 4e-16 at 5 iterations, 2e-14 at 10, 9e-6 at 25, back to
    # 5e-14 at 60), so an iterate taken there pins rounding, not the
    # recurrence.
    for niter in (10, 60):
        xfL = pmt.cgls(OpL, yL, x0L, niter=niter, damp=1e-3, tol=0.0,
                       fused=True)[0]
        xuL = unrolled_cgls(OpL, yL, x0L, niter=niter, damp=1e-3)
        assert np.allclose(xuL.asarray(), xfL.asarray(), rtol=1e-10,
                           atol=1e-10)


def test_implicit_cg_gradient_matches_unrolled(rng):
    """The acceptance pin: implicit fixed-point gradient ≡ unrolled
    tape gradient to ≤1e-5 (f64, converged solve)."""
    Op, dense, xt, y = _spd_problem(rng)
    x0 = _zeros(Op, np.float64)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        Op.shape[1]))

    def via_implicit(y_):
        x = cg_solve(Op, y_, x0, niter=60, tol=0.0)
        return jnp.vdot(w, x._arr.ravel()).real

    def via_unrolled(y_):
        x = unrolled_cg(Op, y_, x0, niter=60)
        return jnp.vdot(w, x._arr.ravel()).real

    gi = jax.grad(via_implicit)(y).asarray()
    gu = jax.grad(via_unrolled)(y).asarray()
    assert np.max(np.abs(gi - gu)) <= 1e-5 * max(
        1.0, float(np.max(np.abs(gu))))
    # analytic: ∇_y ⟨w, A⁻¹y⟩ = A⁻ᵀ w
    ga = np.linalg.solve(dense.T, np.asarray(w))
    assert np.allclose(gi, ga, rtol=1e-6, atol=1e-8)


def test_implicit_cgls_gradient_matches_unrolled(rng):
    Op, dense, y = _ls_problem(rng)
    x0 = _zeros(Op, np.float64)
    damp = 1e-2
    w = jnp.asarray(np.random.default_rng(2).standard_normal(
        Op.shape[1]))

    def via_implicit(y_):
        x = cgls_solve(Op, y_, x0, niter=80, damp=damp, tol=0.0)
        return jnp.vdot(w, x._arr.ravel()).real

    def via_unrolled(y_):
        x = unrolled_cgls(Op, y_, x0, niter=80, damp=damp)
        return jnp.vdot(w, x._arr.ravel()).real

    gi = jax.grad(via_implicit)(y).asarray()
    gu = jax.grad(via_unrolled)(y).asarray()
    assert np.max(np.abs(gi - gu)) <= 1e-5 * max(
        1.0, float(np.max(np.abs(gu))))
    # analytic: ∇_y ⟨w, N⁻¹Aᵀy⟩ = A N⁻ᵀ w,  N = AᵀA + damp²
    N = dense.T @ dense + damp * damp * np.eye(dense.shape[1])
    ga = dense @ np.linalg.solve(N.T, np.asarray(w))
    assert np.allclose(gi, ga, rtol=1e-6, atol=1e-8)


def test_implicit_gradient_under_jit(rng):
    """jit(grad(...)) inlines the unguarded fused builders — the whole
    forward+backward is one compiled program and matches eager."""
    Op, dense, xt, y = _spd_problem(rng)
    x0 = _zeros(Op, np.float64)
    w = jnp.asarray(np.random.default_rng(3).standard_normal(
        Op.shape[1]))

    def loss(y_):
        x = cg_solve(Op, y_, x0, niter=60, tol=0.0)
        return jnp.vdot(w, x._arr.ravel()).real

    ge = jax.grad(loss)(y).asarray()
    gj = jax.jit(jax.grad(loss))(y).asarray()
    assert np.allclose(gj, ge, rtol=1e-12, atol=1e-12)


def test_implicit_param_gradient_fd(rng):
    """Gradient w.r.t. an operator leaf THROUGH the solve (learned-
    operator training seam) finite-difference checks."""
    Op, dense, xt, y = _spd_problem(rng, nblk=8, nloc=4)
    x0 = _zeros(Op, np.float64)
    leaf = jax.tree_util.tree_leaves(Op)[0]
    treedef = jax.tree_util.tree_structure(Op)
    w = jnp.asarray(np.random.default_rng(4).standard_normal(
        Op.shape[1]))

    def loss(lf):
        op = jax.tree_util.tree_unflatten(treedef, [lf])
        x = cg_solve(op, y, x0, niter=60, tol=0.0)
        return jnp.vdot(w, x._arr.ravel()).real

    g = jax.grad(loss)(jnp.asarray(leaf))
    idx = (1, 2, 3)[:np.ndim(leaf)]
    h = 1e-6
    base = np.asarray(leaf)
    vals = []
    for s in (+1, -1):
        pert = base.copy()
        pert[idx] += s * h
        vals.append(float(loss(jnp.asarray(pert))))
    fd = (vals[0] - vals[1]) / (2 * h)
    assert float(g[idx]) == pytest.approx(fd, rel=1e-4, abs=1e-7)


def test_block_implicit_gradients(rng):
    """Block (N, K) carries: one block backward solve covers all K
    cotangent columns; per-column gradients match the single-RHS
    implicit rule."""
    Op, dense, xt, y = _spd_problem(rng)
    K = 3
    cols = np.stack([y.asarray() * (k + 1) for k in range(K)], axis=1)
    yb = DistributedArray.to_dist(cols)
    x0b = DistributedArray.to_dist(
        np.zeros((Op.shape[1], K)))
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        (Op.shape[1], K)))

    def loss_b(yb_):
        x = block_cg_solve(Op, yb_, x0b, niter=60, tol=0.0)
        return jnp.vdot(w, x._arr.reshape(-1, K)).real

    gb = jax.grad(loss_b)(yb).asarray()
    x0 = _zeros(Op, np.float64)
    for k in range(K):
        yk = DistributedArray.to_dist(cols[:, k])

        def loss_k(y_):
            x = cg_solve(Op, y_, x0, niter=60, tol=0.0)
            return jnp.vdot(w[:, k], x._arr.ravel()).real

        gk = jax.grad(loss_k)(yk).asarray()
        assert np.allclose(gb[:, k], gk, rtol=1e-8, atol=1e-10)
    # block cgls smoke: gradient exists and is finite
    OpL, _, yL = _ls_problem(rng)
    ybL = DistributedArray.to_dist(
        np.stack([yL.asarray()] * K, axis=1))
    x0L = DistributedArray.to_dist(np.zeros((OpL.shape[1], K)))

    def loss_ls(yb_):
        x = block_cgls_solve(OpL, yb_, x0L, niter=40, damp=1e-2,
                             tol=0.0)
        return jnp.sum(x._arr * x._arr)

    g = jax.grad(loss_ls)(ybL).asarray()
    assert np.all(np.isfinite(g)) and np.any(g != 0)


def test_x0_zero_cotangent(rng):
    """The converged iterate does not depend on the start: x0's
    cotangent is exactly zero."""
    Op, _, _, y = _spd_problem(rng)
    x0 = DistributedArray.to_dist(
        np.random.default_rng(6).standard_normal(Op.shape[1]))

    def loss(x0_):
        x = cg_solve(Op, y, x0_, niter=60, tol=0.0)
        return jnp.sum(x._arr * x._arr)

    g = jax.grad(loss)(x0).asarray()
    assert np.all(g == 0)


# ------------------------------------------------ off-mode bit identity
def test_autodiff_off_hlo_bit_identical(rng):
    """The tier's off-mode cost is ONE host-side env read: with the
    knob off (or even on — concrete solves never intercept) the
    compiled fused solver programs are byte-identical to the
    knob-unset programs."""
    Op, dense, xt, y = _spd_problem(rng, dtype=np.float32)
    x0 = _zeros(Op, np.float32)

    def f(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=10)

    def g(y_, x_, tol):
        return _cgls_fused(Op, y_, x_, 0.0, tol, niter=10)

    base_f = hlo.compiled_hlo(f, y, x0, 0.0)
    base_g = hlo.compiled_hlo(g, y, x0, 0.0)
    for env in ("off", "on"):
        os.environ["PYLOPS_MPI_TPU_AUTODIFF"] = env
        clear_fused_cache()
        assert _strip(hlo.compiled_hlo(f, y, x0, 0.0)) \
            == _strip(base_f)
        assert _strip(hlo.compiled_hlo(g, y, x0, 0.0)) \
            == _strip(base_g)
        os.environ.pop("PYLOPS_MPI_TPU_AUTODIFF")
    # concrete host entries never intercept even with the knob on
    os.environ["PYLOPS_MPI_TPU_AUTODIFF"] = "on"
    x_on, it_on, _ = pmt.cg(Op, y, x0, niter=10, tol=0.0, fused=True)
    assert isinstance(it_on, int)       # host types, not tracers
    os.environ.pop("PYLOPS_MPI_TPU_AUTODIFF")


# ------------------------------------------------ on-mode entry reroute
def test_entry_reroute_under_jit(rng):
    """PYLOPS_MPI_TPU_AUTODIFF=on: the CLASSIC entries accept traced
    inputs under jit and return the host contract's shapes; values
    match the host solve."""
    os.environ["PYLOPS_MPI_TPU_AUTODIFF"] = "on"
    Op, dense, xt, y = _spd_problem(rng)
    x0 = _zeros(Op, np.float64)
    xh, ith, ch = pmt.cg(Op, y, x0, niter=25, tol=0.0, fused=True)

    @jax.jit
    def jcg(y_):
        x, iiter, cost = pmt.cg(Op, y_, x0, niter=25, tol=0.0)
        return x, iiter, cost

    xj, itj, cj = jcg(y)
    assert np.allclose(xj.asarray(), xh.asarray(), rtol=1e-12,
                       atol=1e-12)
    assert int(itj) == ith

    OpL, _, yL = _ls_problem(rng)
    x0L = _zeros(OpL, np.float64)
    th = pmt.cgls(OpL, yL, x0L, niter=25, damp=1e-3, tol=0.0,
                  fused=True)

    @jax.jit
    def jcgls(y_):
        return pmt.cgls(OpL, y_, x0L, niter=25, damp=1e-3, tol=0.0)

    tj = jcgls(yL)
    assert len(tj) == len(th) == 6
    assert np.allclose(tj[0].asarray(), th[0].asarray(), rtol=1e-12,
                       atol=1e-12)
    assert int(tj[2]) == th[2]                       # iiter
    assert float(tj[4]) == pytest.approx(th[4], rel=1e-10)   # r2norm

    # host-only options refuse under trace instead of mis-tracing
    with pytest.raises(Exception, match="fused path"):
        jax.jit(lambda y_: pmt.cg(Op, y_, x0, niter=5,
                                  callback=lambda *_: None))(y)


def test_entry_reroute_block(rng):
    os.environ["PYLOPS_MPI_TPU_AUTODIFF"] = "on"
    from pylops_mpi_tpu.solvers import block_cg, block_cgls
    Op, dense, xt, y = _spd_problem(rng)
    K = 2
    yb = DistributedArray.to_dist(
        np.stack([y.asarray(), 2 * y.asarray()], axis=1))
    x0b = DistributedArray.to_dist(np.zeros((Op.shape[1], K)))
    xh, ith, ch = block_cg(Op, yb, x0b, niter=25, tol=0.0)

    @jax.jit
    def jb(yb_):
        return block_cg(Op, yb_, x0b, niter=25, tol=0.0)

    xj, itj, cj = jb(yb)
    assert np.allclose(xj.asarray(), xh.asarray(), rtol=1e-12,
                       atol=1e-12)
    tj = jax.jit(lambda yb_: block_cgls(Op, yb_, x0b, niter=10,
                                        damp=1e-3, tol=0.0))(yb)
    assert len(tj) == 6
    assert np.all(np.isfinite(tj[0].asarray()))


# ------------------------------------------------------------ fit
def test_fit_quadratic(rng):
    """The training driver reaches the quadratic's minimum with both
    optimizers, and skips non-inexact leaves."""
    target = jnp.asarray(rng.standard_normal(6))

    def loss(p):
        d = p["w"] - target
        return jnp.vdot(d, d).real

    for optname in ("adam", "sgd"):
        params = {"w": jnp.zeros(6), "n": 3}
        out, losses = fit(loss, params, steps=200, lr=0.1,
                          optimizer=optname)
        assert out["n"] == 3
        assert losses[-1] < 1e-2 * losses[0]
    assert param_count({"w": jnp.zeros(6), "n": 3}) == 6
    assert len(trainable_leaves({"w": jnp.zeros(6), "n": 3})) == 1


def test_fit_learned_scale_through_solver(rng):
    """End-to-end: learn a scalar operator weight through cgls_solve
    (the learned-regularization example's seam, miniature). The scalar
    enters as a ``_ScaledLinearOperator`` pytree leaf — solver scalars
    like ``damp`` stay static."""
    Op, dense, y = _ls_problem(rng, nblk=8, bm=6, bn=4)
    x0 = _zeros(Op, np.float64)
    xt = np.linalg.lstsq(dense, y.asarray(), rcond=None)[0]
    mt = jnp.asarray(xt)

    def loss(log_s):
        # true scale is 1: x(s) = xt/s for the scaled system
        x = cgls_solve(jnp.exp(log_s) * Op, y, x0, niter=60,
                       damp=1e-6, tol=0.0)
        d = x._arr.ravel() - mt
        return jnp.vdot(d, d).real

    p, losses = fit(jax.jit(loss), jnp.asarray(0.5), steps=40, lr=0.2)
    assert losses[-1] < 1e-2 * losses[0]
    assert abs(float(jnp.exp(p)) - 1.0) < 0.1


# ----------------------------------------------- serving signature
def test_familyspec_differentiable_signature():
    from pylops_mpi_tpu.serving.engine import FamilySpec
    from pylops_mpi_tpu.linearoperator import MPILinearOperator
    Op = MPILinearOperator(shape=(8, 8), dtype=np.float64)
    a = FamilySpec("f", Op)
    b = FamilySpec("f", Op, differentiable=False)
    c = FamilySpec("f", Op, differentiable=True)
    assert a.signature() == b.signature()     # default keeps old keys
    assert c.signature() != a.signature()
    assert c.signature()[:len(a.signature())] == a.signature()
