"""Shaped carries (ISSUE 37): the fused CG / CGLS loops hold each
vector of their carry in the N-D shape its operator declares
(``solvers/basic.py::_carry_shape``), one reshape at the loop's entry
and one at its exit around the unchanged flat body. Held here: the
answers equal the flat program's to rounding; the rule's no-side, one
case a word; the event and the span tag that say which solves were
shaped; the metadata the operators read from what they store."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, Partition
from pylops_mpi_tpu import StackedDistributedArray
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.models import poststack_regularized, ricker
from pylops_mpi_tpu.ops import local
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo

WAV = ricker(np.arange(11) * 0.004, 15)[0].astype(np.float32)
CUBE = (8, 8, 128)


def _mesh(ndev):
    if ndev > len(jax.devices()):
        pytest.skip(f"needs {ndev} devices")
    return pmt.make_mesh(ndev)


def _flat_carries(monkeypatch):
    """The helper forced to answer ``None``: every vector flat, the
    program as it was before the rule."""
    real = basic._carry_shape
    monkeypatch.setattr(basic, "_carry_shape",
                        lambda v, dims: real(v, None))


def _poststack(ndev):
    """The stacked post-stack system of ``poststack_3d.reg_cgls``, tiny:
    data modelled from a seeded cube, a smoothed background."""
    mesh = _mesh(ndev)
    rng = np.random.default_rng(37)
    StackOp, Op, _ = poststack_regularized(
        WAV, CUBE[2], CUBE[:2], 1.0, mesh=mesh, dtype=np.float32)
    m = (8 + np.cumsum(rng.standard_normal(CUBE) * 0.05, axis=-1)
         ).astype(np.float32)
    d = Op.matvec(DistributedArray.to_dist(m.ravel(), mesh=mesh))
    back = np.broadcast_to(m.mean(axis=-1, keepdims=True), CUBE)
    x0 = DistributedArray.to_dist(back.ravel().astype(np.float32),
                                  mesh=mesh)
    return StackOp, StackedDistributedArray([d, d.zeros_like()]), x0


def _mdc(ndev):
    """``MPIMDC`` on ``BROADCAST`` vectors: ``(33, 16, 8)`` folds to
    ``(33, 128)``."""
    mesh = _mesh(ndev)
    rng = np.random.default_rng(38)
    nf, ns, nr, nt, nv = 8, 16, 16, 33, 8
    G = (rng.standard_normal((nf, ns, nr))
         + 1j * rng.standard_normal((nf, ns, nr))).astype(np.complex64)
    Op = pmt.MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=1.0, twosided=True,
                    mesh=mesh)
    y = DistributedArray(global_shape=nt * ns * nv, mesh=mesh,
                         partition=Partition.BROADCAST, dtype=np.float32)
    y[:] = jnp.asarray(rng.standard_normal(nt * ns * nv), jnp.float32)
    return Op, y, None


# system, its solve's keywords: x0 given / a zero start / damp > 0 / a
# tol that stops mid-way; MPIMDC from the zero start pmt.cgls makes
# (the donated entry)
CGLS_CASES = {
    "poststack-x0": (_poststack, dict(niter=8, tol=0.0)),
    "poststack-zero-start": (_poststack, dict(niter=8, tol=0.0,
                                              zero=True)),
    "poststack-damp": (_poststack, dict(niter=8, tol=0.0, damp=0.3)),
    "poststack-tol-stops-mid-way": (_poststack, dict(niter=12,
                                                     tol=2e3)),
    "mdc-zero-start": (_mdc, dict(niter=8, tol=0.0)),
}


def _assert_same_solve(a, b, iiter_at):
    """``x``, ``iiter``, ``cost``, ``cost1`` equal to rounding."""
    xa, xb = a[0].asarray(), b[0].asarray()
    assert np.linalg.norm(xa - xb) <= 1e-6 * np.linalg.norm(xb)
    assert a[iiter_at] == b[iiter_at]
    for ca, cb in zip(a[iiter_at + 1:], b[iiter_at + 1:]):
        np.testing.assert_allclose(np.asarray(ca), np.asarray(cb),
                                   rtol=1e-6)


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("guards", [False, True],
                         ids=["guards_off", "guards_on"])
@pytest.mark.parametrize("case", sorted(CGLS_CASES))
def test_cgls_with_shaped_carries_is_the_flat_solve(monkeypatch, case,
                                                    guards, ndev):
    build, kw = CGLS_CASES[case]
    kw = dict(kw)
    Op, y, x0 = build(ndev)
    if kw.pop("zero", False):
        x0 = x0.zeros_like()
    assert basic._carry_tag(Op, x0 if x0 is not None
                            else basic._zero_like_model(Op, y), y,
                            True) == "shaped"

    def solve():
        basic.clear_fused_cache()
        # (x, iiter, cost, cost1, kold, status)
        return basic.cgls_guarded(Op, y, x0=x0, **kw) if guards else \
            (lambda o: (o[0], o[2], o[5], o[4]))(
                pmt.cgls(Op, y, x0=x0, **kw))

    shaped = solve()
    _flat_carries(monkeypatch)
    flat = solve()
    _assert_same_solve(shaped, flat, iiter_at=1)
    if "mid-way" in case:
        assert 0 < shaped[1] < kw["niter"]


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("guards", [False, True],
                         ids=["guards_off", "guards_on"])
def test_cg_with_shaped_carries_is_the_flat_solve(monkeypatch, guards,
                                                  ndev):
    """``pmt.cg`` on ``LapᴴLap + I``: its three vectors are model-side
    and launch through the same rule."""
    mesh = _mesh(ndev)
    Lap = pmt.MPILaplacian(dims=CUBE, axes=(0, 1, 2), weights=(1, 1, 1),
                           sampling=(1, 1, 1), mesh=mesh,
                           dtype=np.float32)
    eye = pmt.MPIBlockDiag([local.Identity(CUBE[1] * CUBE[2] * CUBE[0]
                                           // ndev, dtype=np.float32)
                            for _ in range(ndev)], mesh=mesh)
    Op = Lap.H * Lap + eye
    y = DistributedArray.to_dist(np.random.default_rng(39)
                                 .standard_normal(int(np.prod(CUBE)))
                                 .astype(np.float32), mesh=mesh)
    assert Op.dims == CUBE

    def solve():
        basic.clear_fused_cache()
        return basic.cg_guarded(Op, y, niter=8, tol=0.0) if guards else \
            pmt.cg(Op, y, niter=8, tol=0.0)

    trace.clear_events()
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    shaped = solve()
    ev, = [e["args"] for e in trace.get_events()
           if e["name"] == "solver.carry_select"]
    assert (ev["solver"], ev["shaped"], tuple(ev["model"]), ev["data"]) \
        == ("cg", 1, CUBE, None)
    _flat_carries(monkeypatch)
    flat = solve()
    trace.clear_events()
    _assert_same_solve(shaped, flat, iiter_at=1)


# ------------------------------------------------------ the rule's no-side
def _flat_vec(n, mesh, **kw):
    return DistributedArray(global_shape=n, mesh=mesh, dtype=np.float32,
                            **kw)


def test_folding_reaches_whole_lanes_or_answers_lanes():
    mesh = _mesh(1)
    one = lambda dims: basic._carry_shape(
        _flat_vec(int(np.prod(dims)), mesh), dims)
    assert one((192, 1024, 1024)) == ((192, 1024, 1024), None)
    assert one((1023, 4096, 16)) == ((1023, 65536), None)
    assert one((5, 3, 8, 16)) == ((5, 3, 128), None)
    assert one((5, 3, 8, 48)) == ((5, 3, 384), None)
    assert one((6, 10, 12)) == (None, "lanes")
    assert one((64, 64)) == (None, "lanes")     # whole lanes, one axis


def _blockdiag_matmul(ndev):
    mesh = _mesh(ndev)
    rng = np.random.default_rng(40)
    Op = pmt.MPIBlockDiag([MatrixMult(
        (rng.standard_normal((16, 16)) + 4 * np.eye(16)).astype(
            np.float32)) for _ in range(ndev)], mesh=mesh)
    y = DistributedArray.to_dist(
        rng.standard_normal(16 * ndev).astype(np.float32), mesh=mesh)
    return Op, y, y.zeros_like()


def _summa(ndev):
    mesh = _mesh(ndev)
    rng = np.random.default_rng(41)
    A = (rng.standard_normal((32, 32)) / 6 + 4 * np.eye(32)).astype(
        np.float32)
    Op = pmt.MPIMatrixMult(A, M=8, kind="summa", grid=(2, 2), mesh=mesh,
                           dtype=np.float32)
    y = DistributedArray.to_dist(
        rng.standard_normal(32 * 8).astype(np.float32), mesh=mesh)
    return Op, y, y.zeros_like()


def _laplacian(dims, ndev):
    mesh = _mesh(ndev)
    Op = pmt.MPILaplacian(dims=dims, axes=(0, 1, 2), weights=(1, 1, 1),
                          sampling=(1, 1, 1), mesh=mesh, dtype=np.float32)
    y = DistributedArray.to_dist(
        np.random.default_rng(42).standard_normal(int(np.prod(dims)))
        .astype(np.float32), mesh=mesh)
    return Op, y, y.zeros_like()


NO_SIDE = {
    # word: (system, devices)
    "undeclared": (lambda: _blockdiag_matmul(4)),
    "lanes-summa": (lambda: _summa(4)),           # dims (32, 8)
    "lanes": (lambda: _laplacian((8, 6, 10), 4)),
    "ragged": (lambda: _laplacian((6, 4, 128), 4)),   # 6 inlines, 4 shards
}


@pytest.mark.parametrize("case", sorted(NO_SIDE))
def test_the_no_side_keeps_the_flat_program(monkeypatch, case):
    """Each word of the rule's no-side: the event says it, the span's
    tag says ``flat``, and the compiled solver is the text of
    ``lax.while_loop(cond, body, state)`` itself — the parent's line."""
    word = case.split("-")[0]
    Op, y, x0 = NO_SIDE[case]()
    assert basic._carry_shapes(Op, (x0, y), ("dims", "dimsd")) \
        == ([None, None], word)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    pmt.cgls(Op, y, x0=x0, niter=3, tol=0.0)
    events = trace.get_events()
    trace.clear_events()
    ev, = [e["args"] for e in events if e["name"] == "solver.carry_select"]
    assert (ev["shaped"], ev["model"], ev["data"], ev["why"]) \
        == (0, None, None, word)
    span, = [e["args"] for e in events if e["name"] == "solver.cgls"]
    assert span["carry"] == "flat"

    def text():
        return hlo.strip_provenance(hlo.compiled_hlo(
            lambda op, y, x0: basic._cgls_fused(op, y, x0, 0.0, 0.0,
                                                niter=3), Op, y, x0))

    now = text()
    monkeypatch.setattr(
        basic, "_while_carried",
        lambda solver, Op, cond, body, state, sides: lax.while_loop(
            cond, body, state))
    assert now == text()


def test_a_vector_with_columns_stays_flat():
    mesh = _mesh(1)
    v = DistributedArray(global_shape=(1024, 4), mesh=mesh,
                         dtype=np.float32)
    assert basic._carry_shape(v, (8, 128)) == (None, "columns")
    assert basic._carry_shape(v, (8, 128, 4)) == (None, "columns")


def test_a_stacked_vector_answers_by_component():
    mesh = _mesh(2)
    v = StackedDistributedArray([_flat_vec(4 * 256, mesh),
                                 _flat_vec(6 * 128, mesh),
                                 _flat_vec(5 * 128, mesh)])
    assert basic._carry_shape(v, ((4, 2, 128), (6, 128), (5, 128))) \
        == ([(4, 2, 128), (6, 128), None], "ragged")
    # one tuple a component, or nothing is declared
    assert basic._carry_shape(v, (4, 2, 128)) \
        == ([None, None, None], "undeclared")
    assert basic._carry_shape(v, ((4, 2, 128), (6, 128))) \
        == ([None, None, None], "undeclared")


def test_a_ragged_or_broadcast_split():
    mesh = _mesh(4)
    n = 8 * 128
    even = _flat_vec(n, mesh)
    assert basic._carry_shape(even, (8, 128)) == ((8, 128), None)
    # balanced in elements, but a shard holds no whole rows
    assert basic._carry_shape(even, (2, 512)) == (None, "ragged")
    uneven = _flat_vec(n, mesh, local_shapes=[(384,), (256,), (256,),
                                              (128,)])
    assert basic._carry_shape(uneven, (8, 128)) == (None, "ragged")
    whole = _flat_vec(n, mesh, partition=Partition.BROADCAST)
    assert basic._carry_shape(whole, (2, 512)) == ((2, 512), None)


# ------------------------------------------------- the event and the tag
def test_carry_select_fires_once_a_traced_solve(monkeypatch):
    """One ``solver.carry_select`` a TRACE of the solver (the second
    solve hits the fused cache) with the shapes; the ``carry`` tag rides
    on every ``pmt.solver.cgls`` span."""
    StackOp, y, x0 = _poststack(1)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    basic.clear_fused_cache()
    for _ in range(2):
        pmt.cgls(StackOp, y, x0=x0, niter=2, tol=0.0)
    events = trace.get_events()
    trace.clear_events()
    ev, = [e["args"] for e in events if e["name"] == "solver.carry_select"]
    assert ev["solver"] == "cgls" and ev["shaped"] == 1
    assert tuple(ev["model"]) == CUBE and "why" not in ev
    assert [tuple(d) for d in ev["data"]] == [CUBE, CUBE]
    spans = [e["args"] for e in events if e["name"] == "solver.cgls"]
    assert [s["carry"] for s in spans] == ["shaped", "shaped"]
    assert all(s["normal"] is False for s in spans)


def test_the_unfused_solve_is_tagged_flat():
    StackOp, y, x0 = _poststack(1)
    assert basic._carry_tag(StackOp, x0, y, True) == "shaped"
    assert basic._carry_tag(StackOp, x0, y, False) == "flat"


# --------------------------------------- what the operators read and show
def test_blockdiag_reads_its_cube_from_its_blocks():
    mesh = _mesh(2)
    conv = lambda ny, nx=4: local.Conv1D((ny, nx, 128), jnp.asarray(WAV),
                                         axis=-1, offset=5,
                                         dtype=np.float32)
    assert pmt.MPIBlockDiag([conv(3), conv(5)], mesh=mesh).dims \
        == (8, 4, 128)
    assert pmt.MPIBlockDiag([conv(3), conv(5)], mesh=mesh).dimsd \
        == (8, 4, 128)
    # trailing axes that differ, 1-D blocks, matrices: flat
    ragged = pmt.MPIBlockDiag([conv(3), conv(3, nx=2)], mesh=mesh)
    assert ragged.dims == (ragged.shape[1],)
    Op, *_ = _blockdiag_matmul(2)
    assert (Op.dims, Op.dimsd) == ((32,), (32,))


def test_stacked_vstack_reads_its_children():
    StackOp, *_ = _poststack(1)
    assert StackOp.dims == CUBE and StackOp.dimsd == (CUBE, CUBE)
    mesh = _mesh(1)
    n = int(np.prod(CUBE))
    lap = pmt.MPILaplacian(dims=CUBE, axes=(0, 1, 2), weights=(1, 1, 1),
                           sampling=(1, 1, 1), mesh=mesh,
                           dtype=np.float32)
    flat = pmt.MPIBlockDiag([local.Identity(n, dtype=np.float32)],
                            mesh=mesh)
    mixed = pmt.MPIStackedVStack([lap, flat])   # children disagree
    assert mixed.dims == (n,) and mixed.dimsd == (CUBE, (n,))


def test_a_wrapped_local_operator_and_mdc_show_its_dims():
    f = local.FFT((33, 16, 8), axis=0, real=True, dtype=np.float32)
    wrapped = pmt.aslinearoperator(f)
    assert (wrapped.dims, wrapped.dimsd) == (f.dims, f.dimsd)
    Op, y, _ = _mdc(1)
    assert Op.dims == (33, 16, 8) and Op.dimsd == (33, 16, 8)
    assert basic._carry_shapes(Op, (y, y), ("dims", "dimsd")) \
        == ([(33, 128), (33, 128)], None)
