"""Mixed-precision policy + donation pins (ISSUE 2 tentpole).

Three properties are pinned here, in CI, instead of asserted in prose:

1. **Storage narrowing is policy-driven and bounded**: a bf16-storage
   fused CGLS program may widen each A tile at the GEMM operand — at
   most 2 tile-shaped converts per iteration (matvec + rmatvec) inside
   the while body — and the solver's model/residual vectors are NEVER
   rounded to bf16 (per-iteration vector rounding contaminates the
   Krylov recurrence).
2. **Donation**: the fused solver entries donate the model vector; the
   compiled program must carry an ``input_output_alias`` for it and no
   ``copy`` of the donated parameter.
3. **Dtype stability**: every fused solver (ENGINES × precision)
   converges against the f64 oracle, with bf16 storage tracking f32's
   rel_err within 10× on bf16-representable operators — on such
   operators any residual gap IS recurrence contamination, since the
   two storage modes hold bit-identical matrices.
"""

from functools import partial

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import ml_dtypes
import scipy.linalg as spla

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.ops import _precision as PR
from pylops_mpi_tpu.solvers.basic import (_cg_fused, _cgls_fused,
                                          _cgls_fused_normal)
from pylops_mpi_tpu.utils import hlo as H


@pytest.fixture(autouse=True)
def _reset_policy():
    PR.set_precision(None)
    yield
    PR.set_precision(None)


def _blocks(rng, nblk=8, n=16, representable=True, spd=False):
    """Well-conditioned diagonally-dominant f32 blocks, quantized to
    the bf16 grid so f32 and bf16 storage hold the identical matrix."""
    mats = []
    for _ in range(nblk):
        b = (rng.standard_normal((n, n)) / 4).astype(np.float32)
        if spd:
            b = (b @ b.T).astype(np.float32)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        if representable:
            b = b.astype(ml_dtypes.bfloat16).astype(np.float32)
        mats.append(b)
    return mats


# ------------------------------------------------------------ policy seam
def test_policy_env_seam(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECISION", "bf16")
    PR.set_precision(None)  # re-resolve from env
    pol = PR.get_policy()
    assert pol.name == "bf16"
    assert PR.default_compute_dtype(np.float32) == np.dtype(jnp.bfloat16)
    # f64 is the oracle precision: never narrowed
    assert PR.default_compute_dtype(np.float64) is None
    assert PR.default_compute_dtype(np.complex128) is None
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECISION", "f32")
    PR.set_precision(None)
    assert PR.get_policy().name == "f32"
    assert PR.default_compute_dtype(np.float32) is None


def test_policy_unknown_value_warns_and_falls_back(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_PRECISION", "fp8_exotic")
    with pytest.warns(UserWarning, match="fp8_exotic"):
        PR.set_precision(None)
        assert PR.get_policy().name == "f32"


def test_c64_policy_narrows_complex_only():
    PR.set_precision("c64")
    assert PR.default_compute_dtype(np.complex128) == np.dtype(np.complex64)
    assert PR.default_compute_dtype(np.float32) is None


def test_reduction_and_accum_dtypes():
    assert PR.reduction_dtype(jnp.bfloat16) == np.dtype(np.float32)
    assert PR.reduction_dtype(np.float32) == np.dtype(np.float32)
    assert PR.reduction_dtype(np.float64) == np.dtype(np.float64)
    assert PR.reduction_dtype(np.complex64) == np.dtype(np.float32)
    assert PR.reduction_dtype(np.complex128) == np.dtype(np.float64)
    assert PR.accum_dtype(jnp.bfloat16) == np.dtype(np.float32)
    assert PR.accum_dtype(np.complex64) == np.dtype(np.complex64)
    assert PR.accum_dtype(np.float64) == np.dtype(np.float64)


def test_operators_consume_policy(rng):
    PR.set_precision("bf16")
    mats = _blocks(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    assert np.dtype(Op.compute_dtype) == np.dtype(jnp.bfloat16)
    assert Op._batched.dtype == jnp.bfloat16
    # explicit override beats the policy
    Op32 = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats],
                        compute_dtype=np.float32)
    assert Op32._batched.dtype == jnp.float32
    # f64 operators are untouched by the bf16 policy
    Op64 = MPIBlockDiag([MatrixMult(m.astype(np.float64),
                                    dtype=np.float64) for m in mats])
    assert Op64.compute_dtype is None


def test_matrixmult_consumes_policy(rng):
    PR.set_precision("bf16")
    A = rng.standard_normal((32, 24)).astype(np.float32)
    Op = pmt.MPIMatrixMult(A, M=8, kind="summa", dtype=np.float32)
    assert np.dtype(Op.compute_dtype) == np.dtype(jnp.bfloat16)
    assert Op.Ap.dtype == jnp.bfloat16


# ------------------------------------------- the narrow-contraction rule
def test_einsum_narrow_never_rounds_the_vector(rng):
    """The vector operand enters the contraction at ITS dtype: if it
    were narrowed per call (the pre-ISSUE-2 behavior), the result would
    differ from the wide-vector oracle on vectors that are not
    bf16-representable."""
    A = jnp.asarray(rng.standard_normal((4, 16, 16)).astype(np.float32))
    Ab = A.astype(jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((4, 16, 1)).astype(np.float32))
    got = PR.einsum_narrow("bmn,bnk->bmk", Ab, v, jnp.bfloat16,
                           np.float32)
    assert got.dtype == jnp.float32
    want = jnp.einsum("bmn,bnk->bmk", Ab.astype(jnp.float32), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    rounded = jnp.einsum("bmn,bnk->bmk", Ab, v.astype(jnp.bfloat16),
                         preferred_element_type=np.float32)
    # sanity: rounding v actually changes the answer at this shape
    assert np.abs(np.asarray(got) - np.asarray(rounded)).max() > 0


def test_narrow_vector_space_reduces_at_f32(rng):
    """bf16 vector spaces accumulate dots/norms at f32 (the reduction
    floor): the result dtype is f32 and the value matches a f32
    accumulation oracle, not a bf16 one."""
    v = rng.standard_normal(4096).astype(np.float32)
    d = DistributedArray.to_dist(jnp.asarray(v).astype(jnp.bfloat16))
    got = d.dot(d)
    assert jnp.asarray(got).dtype == jnp.float32
    vb = np.asarray(jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(float(got), float((vb * vb).sum()),
                               rtol=1e-4)
    assert jnp.asarray(d.norm()).dtype == jnp.float32


# --------------------------------------------------------- HLO: converts
def _flagship_like(rng, n=32, dtype=np.float32):
    mats = _blocks(rng, nblk=8, n=n)
    y = rng.standard_normal(8 * n).astype(dtype)
    dy = DistributedArray.to_dist(y)
    x0 = DistributedArray.to_dist(np.zeros(8 * n, dtype=dtype))
    return mats, dy, x0


def test_fused_cgls_bf16_tile_convert_budget(rng):
    """The bf16-storage fused CGLS program holds ≤2 A-tile-shaped
    dtype-converts per iteration inside the while body (matvec +
    rmatvec operand widens; XLA may also hoist them out entirely, which
    trivially satisfies the pin) — per-element wide copies of the block
    stack beyond that are the HBM-doubling regression this guards."""
    PR.set_precision("bf16")
    mats, dy, x0 = _flagship_like(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    assert Op._batched.dtype == jnp.bfloat16
    jfn = jax.jit(lambda op, y, x, damp, tol: partial(
        _cgls_fused, niter=20)(op, y, x, damp, tol))
    hlo = H.compiled_hlo(jfn, Op, dy, x0, 0.0, 0.0)
    # tile shape per shard: [1,32,32] (or the unsharded [8,32,32])
    shape_re = r"\[(?:1|8),32,32\]"
    in_body = H.count_ops(hlo, "convert", shape_re=shape_re,
                          computation_re=r"body|while|region")
    assert in_body <= 2, f"{in_body} A-tile converts inside the loop body"
    total = H.count_ops(hlo, "convert", shape_re=shape_re)
    # setup (matvec+rmatvec+matvec) + body (matvec+rmatvec), some CSE'd
    assert total <= 6, f"{total} A-tile converts in the whole program"


def test_fused_cgls_bf16_no_narrow_vector_ops(rng):
    """No vector-shaped bf16 buffer may appear in the bf16-storage
    fused CGLS program: bf16 touches the block stack only, never the
    while-loop carries (x/s/c/q stay f32)."""
    PR.set_precision("bf16")
    mats, dy, x0 = _flagship_like(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    jfn = jax.jit(lambda op, y, x, damp, tol: partial(
        _cgls_fused, niter=20)(op, y, x, damp, tol))
    hlo = H.compiled_hlo(jfn, Op, dy, x0, 0.0, 0.0)
    import re
    # bf16 vector shapes (1-D, any length) = rounded solver state
    bad = [ln.strip()[:140] for ln in hlo.splitlines()
           if re.search(r"bf16\[\d+\]", ln)]
    assert not bad, "bf16 vector buffers in the program:\n" + "\n".join(
        bad[:6])


# --------------------------------------------------------- HLO: donation
def test_fused_cgls_donation(rng):
    """The fused CGLS entry donates x0: the compiled program aliases it
    to an output and never copies the donated parameter — the loop
    carry starts in the caller's buffer (zero copies of donated
    while_loop state, ISSUE 2 acceptance)."""
    mats, dy, x0 = _flagship_like(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    jfn = jax.jit(lambda op, y, x, damp, tol: partial(
        _cgls_fused, niter=20)(op, y, x, damp, tol), donate_argnums=(2,))
    rep = H.assert_donation(jfn, Op, dy, x0, 0.0, 0.0)
    assert rep["donated_param_copies"] == 0


def test_fused_cg_donation(rng):
    mats, dy, x0 = _flagship_like(rng)
    spd = [(m @ m.T + 4 * np.eye(m.shape[0])).astype(np.float32)
           for m in mats]
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in spd])
    jfn = jax.jit(lambda op, y, x, tol: partial(
        _cg_fused, niter=20)(op, y, x, tol), donate_argnums=(2,))
    H.assert_donation(jfn, Op, dy, x0, 0.0)


def test_public_api_preserves_caller_x0(rng):
    """Donation must never invalidate a caller-owned x0: the public
    wrappers copy before donating, so repeated solves with one x0
    work."""
    mats = _blocks(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    dense = spla.block_diag(*mats)
    xt = rng.standard_normal(8 * 16).astype(np.float32)
    dy = DistributedArray.to_dist((dense @ xt).astype(np.float32))
    x0 = DistributedArray.to_dist(np.zeros(8 * 16, dtype=np.float32))
    x1, *_ = pmt.cgls(Op, dy, x0, niter=40, tol=0.0)
    x2, *_ = pmt.cgls(Op, dy, x0, niter=40, tol=0.0)  # x0 still alive
    np.testing.assert_allclose(np.asarray(x1.asarray()),
                               np.asarray(x2.asarray()), rtol=1e-6)


def test_donation_gate_env(rng, monkeypatch):
    """PYLOPS_MPI_TPU_DONATE=0 disables donation (and the cache keys
    the two modes apart, so flipping mid-session retraces instead of
    reusing an executable with the wrong aliasing contract)."""
    mats = _blocks(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    dy = DistributedArray.to_dist(
        rng.standard_normal(8 * 16).astype(np.float32))
    x0 = dy.zeros_like()
    r1 = pmt.cgls(Op, dy, x0, niter=10, tol=0.0)
    monkeypatch.setenv("PYLOPS_MPI_TPU_DONATE", "0")
    assert not PR.donation_enabled()
    r2 = pmt.cgls(Op, dy, x0, niter=10, tol=0.0)
    np.testing.assert_allclose(np.asarray(r1[0].asarray()),
                               np.asarray(r2[0].asarray()), rtol=1e-6)


# ------------------------------------ ENGINES × precision vs f64 oracle
def _oracle_problem(rng, spd):
    mats = _blocks(rng, spd=spd)
    dense = spla.block_diag(*mats).astype(np.float64)
    xt = rng.standard_normal(8 * 16)
    y64 = dense @ xt
    return mats, dense, xt, y64


def _rel_err(x, xs):
    x = np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(x - xs) / np.linalg.norm(xs))


ENGINES = ["cg", "cgls", "cgls_normal", "ista", "fista", "power"]


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("engine", ENGINES)
def test_engine_precision_vs_f64_oracle(rng, engine, precision):
    """Every fused solver, at every storage precision, against the f64
    oracle — and the bf16-storage run tracks the f32 run within 10× on
    rel_err (the dtype-stability acceptance: with bf16-representable
    blocks both precisions solve the identical system, so a bf16 cliff
    here is recurrence contamination, the round-5 ``bf16_race`` prime
    suspect)."""
    spd = engine in ("cg", "power")
    mats, dense, xt, y64 = _oracle_problem(rng, spd=spd)

    def solve(policy):
        PR.set_precision(policy)
        pmt.clear_fused_cache()
        Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32)
                           for m in mats])
        if policy == "bf16":
            assert Op._batched.dtype == jnp.bfloat16
        y32 = (dense @ xt).astype(np.float32)
        dy = DistributedArray.to_dist(y32)
        if engine == "cg":
            x, *_ = pmt.cg(Op, dy, niter=120, tol=0.0)
            return _rel_err(x.asarray(), np.linalg.solve(dense, y64))
        if engine in ("cgls", "cgls_normal"):
            x, *_ = pmt.cgls(Op, dy, niter=120, tol=0.0,
                             normal=(engine == "cgls_normal"))
            xs = np.linalg.lstsq(dense, y64, rcond=None)[0]
            return _rel_err(x.asarray(), xs)
        if engine in ("ista", "fista"):
            fn = pmt.ista if engine == "ista" else pmt.fista
            x0 = dy.zeros_like()
            # tiny eps: the solve approaches the least-squares solution
            x, *_ = fn(Op, dy, x0=x0, niter=200, eps=1e-6, tol=0.0)
            xs = np.linalg.lstsq(dense, y64, rcond=None)[0]
            return _rel_err(x.asarray(), xs)
        if engine == "power":
            from pylops_mpi_tpu.solvers.eigs import power_iteration
            x0 = dy.zeros_like()
            maxeig, _, _ = power_iteration(Op.H @ Op, b_k=x0, niter=60,
                                           tol=0.0, dtype=np.float32)
            want = float(np.linalg.norm(dense, 2) ** 2)
            return abs(abs(maxeig) - want) / want
        raise AssertionError(engine)

    err_f32 = solve("f32")
    # power iteration's eigenvalue converges geometrically in the
    # (small) spectral gap — a looser absolute bound than the solves
    bound = 2e-2 if engine == "power" else 5e-4
    assert err_f32 < bound, f"{engine} f32 off the f64 oracle: {err_f32}"
    if precision == "bf16":
        err_b = solve("bf16")
        # within 10× of f32's rel_err (+ small absolute floor so an
        # exactly-converged f32 run does not make the bound vacuous)
        assert err_b <= 10 * err_f32 + 1e-6, (
            f"{engine}: bf16 {err_b:.2e} vs f32 {err_f32:.2e} — "
            "recurrence contamination")


def test_carry_dtypes_stable_iteration_1_vs_k(rng):
    """Direct pin on the prime suspect: the while-loop carry pytree of
    the bf16-storage fused CGLS has the same dtypes entering iteration
    1 and iteration k (jaxpr-level check on the loop body), and no
    carry leaf is bf16."""
    PR.set_precision("bf16")
    mats, dy, x0 = _flagship_like(rng)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    jaxpr = jax.make_jaxpr(lambda op, y, x: partial(
        _cgls_fused, niter=10)(op, y, x, 0.0, 0.0))(Op, dy, x0)
    whiles = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert whiles, "fused CGLS must lower to a while loop"
    body = whiles[0].params["body_jaxpr"].jaxpr
    # body invars = [*consts, *carry]: compare the carry suffix only
    # (the consts legitimately include the bf16 block stack)
    nc = whiles[0].params["body_nconsts"]
    in_dt = [v.aval.dtype for v in body.invars[nc:]]
    out_dt = [v.aval.dtype for v in body.outvars]
    assert in_dt == out_dt, "carry dtypes change across iterations"
    assert not any(dt == jnp.bfloat16 for dt in out_dt), \
        "a while-loop carry is bf16: solver state was narrowed"


# ----------------------------------------------- pallas streaming kernel
def test_pallas_pick_tile_bf16_sublane():
    """bf16 blocks need 16-divisible row tiles (Mosaic packed-tile
    rule); f32 allows 8."""
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    assert pk._pick_tile(24, 128, 4, min_sublane=8) == 8
    # 24 % 16 != 0 → falls through to the whole-dim block
    assert pk._pick_tile(24, 128, 4, min_sublane=16) == 24
    assert pk._pick_tile(32, 128, 2, min_sublane=16) == 32
    assert pk._min_sublane(jnp.bfloat16) == 16
    assert pk._min_sublane(np.float32) == 8


def test_pallas_streaming_normal_matvec_bf16(rng):
    """The bf16-tile-streaming kernel: A stored bf16, x f32, outputs
    f32, accuracy against the f32-widened oracle (exact on
    bf16-representable blocks up to f32 accumulation order)."""
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    A = jnp.asarray(np.stack(_blocks(rng, nblk=4, n=32)))
    Ab = A.astype(jnp.bfloat16)
    X = jnp.asarray(rng.standard_normal((4, 32)).astype(np.float32))
    assert pk.normal_matvec_supported(Ab)
    u, q = pk.batched_normal_matvec(Ab, X[:, None, :])    # K = 1
    assert u.dtype == jnp.float32 and q.dtype == jnp.float32
    u, q = u[:, 0], q[:, 0]
    qs = np.einsum("bmn,bn->bm", np.asarray(A), np.asarray(X))
    us = np.einsum("bmn,bm->bn", np.asarray(A), qs)
    np.testing.assert_allclose(np.asarray(q), qs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(u), us, rtol=1e-4, atol=1e-4)


def test_blockdiag_normal_matvec_bf16_storage(rng):
    """MPIBlockDiag.normal_matvec with bf16 storage and an f32 vector
    routes through the streaming kernel and matches the two-sweep
    oracle."""
    PR.set_precision("bf16")
    mats = _blocks(rng, nblk=8, n=32)
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    if not Op.has_fused_normal:
        pytest.skip("no fused-normal path on this backend")
    x = DistributedArray.to_dist(
        rng.standard_normal(8 * 32).astype(np.float32))
    u, q = Op.normal_matvec(x)
    q2 = Op.matvec(x)
    u2 = Op.rmatvec(q2)
    np.testing.assert_allclose(np.asarray(u.asarray()),
                               np.asarray(u2.asarray()), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(q.asarray()),
                               np.asarray(q2.asarray()), rtol=2e-4,
                               atol=2e-4)


# ------------------------------------------------------ hlo tool parsing
def test_count_ops_and_donation_parse_synthetic():
    hlo = """HloModule jit_f, input_output_alias={ {0}: (2, {}, may-alias), {1}: (1, {}, may-alias) }, entry_computation_layout={()->()}

%region_1.23 (p: f32[8,32,32]) -> f32[8,32,32] {
  %convert.1 = f32[8,32,32]{2,1,0} convert(bf16[8,32,32]{2,1,0} %p)
  %convert.2 = f32[16]{0} convert(bf16[16]{0} %q)
}

ENTRY %main.9 (Arg_0.1: f32[8], Arg_1.2: f32[8], Arg_2.3: f32[8]) -> f32[8] {
  %convert.3 = f32[8,32,32]{2,1,0} convert(bf16[8,32,32]{2,1,0} %c)
  %copy.1 = f32[8]{0} copy(f32[8]{0} %Arg_0.1)
}
"""
    assert H.count_ops(hlo, "convert") == 3
    assert H.count_ops(hlo, "convert", shape_re=r"\[8,32,32\]") == 2
    assert H.count_ops(hlo, "convert", shape_re=r"\[8,32,32\]",
                       computation_re=r"region") == 1
    rep = H.parse_donation(hlo)
    assert rep["aliased_params"] == [1, 2]
    assert rep["donated_param_copies"] == 0  # Arg_0 is not donated
    hlo_bad = hlo.replace("copy(f32[8]{0} %Arg_0.1)",
                          "copy(f32[8]{0} %Arg_2.3)")
    assert H.parse_donation(hlo_bad)["donated_param_copies"] == 1
