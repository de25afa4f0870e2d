"""The Solvers layer's own names (ISSUE 36): the scopes
``pmt.solver.setup`` / ``step`` / ``direction`` / ``cost`` on the
recurrence's own passes inside the fused CGLS programs, and the host
spans ``pmt.solver.launch`` / ``collect`` that tile the wrapper around
them. Names are provenance: the programs are what they were."""

from __future__ import annotations

import contextlib
import re
import threading

import numpy as np
import pytest

import jax

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.solvers import basic, block
from pylops_mpi_tpu.utils import hlo

NDEV = len(jax.devices())

OWN = ("pmt.solver.setup", "pmt.solver.step", "pmt.solver.direction",
       "pmt.solver.cost")


def _problem(K=None, n=16):
    rng = np.random.default_rng(36)
    blocks = [(rng.standard_normal((n, n)) + 4 * np.eye(n)
               ).astype(np.float32) for _ in range(NDEV)]
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                           for b in blocks])
    if K is None:
        y = pmt.DistributedArray.to_dist(
            rng.standard_normal(NDEV * n).astype(np.float32))
    else:
        y = pmt.DistributedArray(global_shape=(NDEV * n, K),
                                 dtype=np.float32)
        y[:] = rng.standard_normal((NDEV * n, K)).astype(np.float32)
    return Op, y


def _program(solver: str, normal: bool, guards: bool) -> str:
    """Optimized HLO of one fused CGLS program, as its wrapper builds
    it."""
    Op, y = _problem(K=3 if solver == "block_cgls" else None)
    if normal and not Op.has_fused_normal:
        pytest.skip("no fused normal kernel on this backend")
    if solver == "cgls":
        fn = basic._cgls_fused_normal if normal else basic._cgls_fused

        def run(y, x, damp, tol):
            return fn(Op, y, x, damp, tol, niter=3, guards=guards,
                      stall_n=5)
    else:
        def run(y, x, damp, tol):
            return block._block_cgls_fused(
                Op, y, x, damp, tol, niter=3, normal=normal,
                guards=guards, stall_n=5)
    return hlo.compiled_hlo(run, y, y.zeros_like(), 0.1, 0.0)


PROGRAMS = [pytest.param(s, n, g, id=f"{s}-{'one' if n else 'two'}_sweep"
                         f"-guards_{'on' if g else 'off'}")
            for s in ("cgls", "block_cgls") for n in (False, True)
            for g in (False, True)]


@pytest.mark.parametrize("solver,normal,guards", PROGRAMS)
def test_own_scopes_survive_apart_from_every_operator(solver, normal,
                                                      guards):
    """(a) the four names are in the compiled program's ``op_name``s,
    ``setup`` before the loop and the other three inside the ``while``
    body, and no op carries one of them together with an operator's
    scope: ``operator_split`` keys every op as before."""
    names = re.findall(r'op_name="([^"]*)"', _program(solver, normal,
                                                     guards))
    inside = [n for n in names if "/while/body/" in n]
    assert inside
    for scope in OWN[1:]:
        assert any(scope in n.split("/") for n in inside), scope
        assert not any(scope in n.split("/") for n in names
                       if n not in inside), scope
    assert any(OWN[0] in n.split("/") for n in names)
    assert not any(OWN[0] in n.split("/") for n in inside)
    for n in names:
        parts = [p for p in n.split("/") if p.startswith("pmt.")]
        if any(p in OWN for p in parts):
            assert all(p in OWN for p in parts), n
    assert any("/pmt.MPIBlockDiag." in n for n in inside)


@pytest.mark.parametrize("solver,normal,guards", PROGRAMS)
def test_own_scopes_are_provenance_only(monkeypatch, solver, normal,
                                        guards):
    """(b) less its provenance the program is the one built with the
    solver's scopes patched out."""
    named = _program(solver, normal, guards)
    real = trace._annotation

    def bare(name, tags, tracing):
        if tracing and name.startswith("solver."):
            return contextlib.nullcontext()
        return real(name, tags, tracing)

    monkeypatch.setattr(trace, "_annotation", bare)
    plain = _program(solver, normal, guards)
    assert "pmt.solver." in named and "pmt.solver." not in plain
    assert "pmt.MPIBlockDiag." in plain
    assert hlo.strip_provenance(named) == hlo.strip_provenance(plain)


@pytest.mark.parametrize("solver,normal,guards", PROGRAMS)
def test_flat_dims_keep_the_loop_as_it_was(monkeypatch, solver, normal,
                                           guards):
    """(ISSUE 37) ``MPIBlockDiag(MatrixMult)`` declares no N-D ``dims``:
    the loop-launching rule answers flat and the program is the text
    of ``lax.while_loop(cond, body, state)`` itself, the line the rule
    replaced (the block solvers never go through it)."""
    from jax import lax
    now = _program(solver, normal, guards)
    monkeypatch.setattr(
        basic, "_while_carried",
        lambda solver, Op, cond, body, state, sides: lax.while_loop(
            cond, body, state))
    assert hlo.strip_provenance(now) \
        == hlo.strip_provenance(_program(solver, normal, guards))


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: the opens and
    closes of every ``pmt.solver.*`` annotation, with the thread."""

    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        if self.name.startswith("pmt.solver."):
            _Recorder.log.append(("open", self.name, self.kw,
                                  threading.get_ident()))
        return self

    def __exit__(self, *exc):
        if self.name.startswith("pmt.solver."):
            _Recorder.log.append(("close", self.name, self.kw,
                                  threading.get_ident()))
        return False


@pytest.mark.parametrize("guards", (False, True),
                         ids=("guards_off", "guards_on"))
@pytest.mark.parametrize("solver,K", (("cgls", None), ("block_cgls", 1),
                                      ("block_cgls", 3)),
                         ids=("cgls", "block_cgls-K1", "block_cgls-K3"))
def test_one_launch_and_one_collect_a_solve(monkeypatch, solver, K,
                                            guards):
    """(d) every solve opens, on the calling thread and inside the
    wrapper's own span, one ``pmt.solver.launch`` and then one
    ``pmt.solver.collect``; the first ends before the second starts."""
    Op, y = _problem(K=K)
    call = pmt.cgls if solver == "cgls" else pmt.block_cgls
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "log", [])
    answers = [call(Op, y, niter=3, tol=0.0, guards=guards)[0].asarray()
               for _ in range(2)]          # a compile, then a cache hit
    np.testing.assert_array_equal(*answers)
    outer = "pmt.solver." + solver
    tag = {"solver": "cgls" if K in (None, 1) else "block_cgls"}
    solve = [("open", outer), ("open", "pmt.solver.launch"),
             ("close", "pmt.solver.launch"),
             ("open", "pmt.solver.collect"),
             ("close", "pmt.solver.collect"), ("close", outer)]
    assert [(what, name) for what, name, _, _ in _Recorder.log] \
        == solve * 2
    assert {tid for *_, tid in _Recorder.log} == {threading.get_ident()}
    assert all(kw == tag for _, name, kw, _ in _Recorder.log
               if name != outer)


def test_ring_buffer_names_the_two_phases(monkeypatch):
    """Under ``PYLOPS_MPI_TPU_TRACE=spans`` the same two phases land in
    the ring buffer as children of the solver's span."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    Op, y = _problem()
    pmt.cgls(Op, y, niter=2, tol=0.0)
    root, = [n for n in trace.span_tree() if n["name"] == "solver.cgls"]
    assert [c["name"] for c in root["children"]
            if c["name"].startswith("solver.")] \
        == ["solver.launch", "solver.collect"]
    trace.clear_events()
