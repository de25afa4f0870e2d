"""The readers of the Solvers layer's own names (ISSUE 36,
``chipbench/solver_phases.py`` and the six layers on it), on
synthesized traces: the parts sum to what the subtraction and
``idle_split`` read, a parent's trace reads nothing and says so, a
clock violation silences them. No chip needed."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from chipbench import program_trace as P  # noqa: E402
from chipbench import solver_phases as S  # noqa: E402
from chipbench.layers import (idle_awaiting_input_pct,  # noqa: E402
                              idle_in_launch_pct, iter_device_ms,
                              launch_host_ms, operator_device_ms,
                              solver_cost_device_ms,
                              solver_self_device_ms,
                              solver_update_device_ms, unscoped_device_ms)
from chipbench.tests.test_program_trace import (DEV, batch,  # noqa: E402
                                                ctx_of)

OWN = (solver_update_device_ms, solver_cost_device_ms, unscoped_device_ms)
CLOSED = OWN + (launch_host_ms,)
SERVED = (idle_in_launch_pct, idle_awaiting_input_pct)
BODY = "jit(f)/while/body/"


# ------------------------------------------------------ a closed loop
def solved(named=True, first_op_at=1150):
    """Two solves of two iterations, slice 0..10000. A solve: one op
    of the set-up, then the loop's — two applies, the recurrence's own
    passes under their scopes, a copy that carries no ``op_name`` and
    an ``add`` whose path holds no ``pmt.`` component. ``named=False``
    is the parent's trace: the same ops and spans without the solver's
    names."""
    def op(name, at, dur, path):
        if not named:
            path = path.replace("pmt.solver.setup/", "").replace(
                "pmt.solver.step/", "").replace(
                "pmt.solver.direction/", "").replace(
                "pmt.solver.cost/", "")
        return (name, at, dur, None, {"tf_op": path} if path else None)

    ops, host = [], [("cb.slice", 0, 10000)]
    for t, launch in ((0, 80), (5000, 100)):
        ops += [op("%sub.1 = f32[] subtract()", t + first_op_at, 40,
                   "jit(f)/pmt.solver.setup/sub"),
                ("%while.3 = () while()", t + 1200, 3700),
                op("%fusion.24 = f32[] fusion()", t + 1300, 1000,
                   BODY + "pmt.MPIBlockDiag.matvec/dot_general"),
                op("%fusion.21 = f32[] fusion()", t + 2300, 1000,
                   BODY + "pmt.MPIBlockDiag.rmatvec/dot_general"),
                op("%multiply_reduce_fusion.2 = f32[] fusion()", t + 3300,
                   300, BODY + "pmt.solver.step/reduce_sum"),
                op("%copy.7 = f32[] copy()", t + 3600, 60, ""),
                op("%fusion.5 = f32[] fusion()", t + 3660, 50,
                   BODY + "pmt.solver.direction/add"),
                op("%multiply_reduce_fusion.9 = f32[] fusion()", t + 3710,
                   100, BODY + "pmt.solver.cost/reduce_sum"),
                op("%add.7 = s32[] add()", t + 3810, 30, BODY + "add")]
        host += [("cb.solve", t + 1000, 4000),
                 ("pmt.solver.cgls", t + 1100, 3890, {"niter": 2})]
        if named:
            host += [("pmt.solver.launch", t + 1110, launch,
                      {"solver": "cgls"}),
                     ("pmt.solver.collect", t + 1110 + launch,
                      3870 - launch, {"solver": "cgls"})]
    return [(DEV, [("XLA Ops", ops)]), ("/host:CPU", [("main", host)])]


def test_own_split_sums_to_the_subtraction(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, solved(),
                 {"iterations_per_solve": 2})
    split = S.own_split(ctx)
    # two solves x two iterations: every op of a solve counts a half
    assert split == {"cost": pytest.approx(50 / 1e6),
                     "direction": pytest.approx(25 / 1e6),
                     "setup": pytest.approx(20 / 1e6),
                     "step": pytest.approx(150 / 1e6),
                     "unscoped": pytest.approx(45 / 1e6)}
    update, cost, unscoped = (m.read(ctx) for m in OWN)
    assert update == pytest.approx(195 / 1e6)
    assert cost == pytest.approx(50 / 1e6)
    assert unscoped == pytest.approx(45 / 1e6)
    assert iter_device_ms.read(ctx) == pytest.approx(1290 / 1e6)
    assert operator_device_ms.read(ctx) == pytest.approx(1000 / 1e6)
    assert update + cost + unscoped \
        == pytest.approx(solver_self_device_ms.read(ctx))
    said, = [m for m in ctx["said"] if "solver's own device ms" in m]
    assert "step 0.000" in said and "unscoped 0.000" in said
    assert said.index("copy.7") < said.index("add.7")   # largest first


def test_launch_host_ms_is_the_launch_spans_median(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, solved(),
                 {"iterations_per_solve": 2})
    assert launch_host_ms.read(ctx) == pytest.approx(90 / 1e6)
    found = S.host_phases(ctx)
    assert found["solves"] == 2
    assert found["before_launch_ms"] == pytest.approx(10 / 1e6)
    assert found["collect_ms"] == pytest.approx(3780 / 1e6)
    # up to launch's end the device waits 50 and 70 (its first op runs
    # inside launch), 110 and 90 between the set-up's op and the
    # loop's first, and after the last scoped op all but add.7's 30
    assert found["idle_ms"] == {
        "launch": pytest.approx(60 / 1e6), "input": 0.0,
        "loop": pytest.approx(100 / 1e6),
        "tail": pytest.approx(1150 / 1e6)}
    assert any("2 pmt.solver.cgls spans" in m for m in ctx["said"])


# ----------------------------------------------------------- a service
def served(named=True, slice_ns=20000):
    """``test_program_trace.served``'s two batches with the solver's
    spans inside each ``pmt.serve.solve`` and scoped ops: in batch 1 a
    small unscoped program (a fresh ``x0``) runs before the fused
    one, whose first op begins after ``launch`` has ended; in batch 2
    it begins while ``launch`` is still open."""
    def op(name, at, dur):
        return (name, at, dur, None,
                {"tf_op": BODY + "pmt.MPIBlockDiag.normal_matvec/dot"})

    ops = [("%broadcast.1 = f32[] broadcast()", 3160, 20),
           ("%while.1 = () while()", 3200, 4700),
           op("%fusion.1 = f32[] fusion()", 3200, 1800),
           op("%fusion.2 = f32[] fusion()", 5200, 2600),
           op("%fusion.1 = f32[] fusion()", 13100, 2900),
           op("%fusion.2 = f32[] fusion()", 16000, 2900)]
    disp = [("pmt.serve.collect", 500, 1500, {"batch": 1})] \
        + batch(1, 2000, 300, 700, 5000, 600, 400) \
        + [("pmt.serve.collect", 9000, 3000, {"batch": 2})] \
        + batch(2, 12000, 400, 600, 6000, 500, 500) \
        + [("pmt.serve.collect", 20000, 2000, {"batch": 3})]
    for t, launch in ((3000, 100), (13000, 130)):
        disp += [("pmt.solver.block_cgls", t + 10, 4980, {"batch": 4})]
        if named:
            disp += [("pmt.solver.launch", t + 50, launch,
                      {"solver": "block_cgls"}),
                     ("pmt.solver.collect", t + 50 + launch, 4900 - launch,
                      {"solver": "block_cgls"})]
    main = [("cb.slice", 1000, slice_ns), ("cb.submit", 1500, 100)]
    return [(DEV, [("XLA Ops", ops)]),
            ("/host:CPU", [("main", main),
                           ("pylops-serve-dispatch", sorted(
                               disp, key=lambda ev: ev[1]))])]


@pytest.mark.parametrize("slice_ns,parts", (
    (20000, {"launch": 250, "input": 30, "loop": 200, "tail": 300}),
    # the slice ends inside batch 2's solve: its head alone counts
    (15000, {"launch": 250, "input": 30, "loop": 200, "tail": 200}),
), ids=("whole_spans", "a_span_the_slice_cuts"))
def test_host_phases_sum_to_idle_splits_solve(tmp_path, monkeypatch,
                                              slice_ns, parts):
    ctx = ctx_of(tmp_path, monkeypatch, served(slice_ns=slice_ns), {})
    found = S.host_phases(ctx)
    assert found["idle_pct"] == {
        k: pytest.approx(100.0 * v / slice_ns) for k, v in parts.items()}
    assert sum(found["idle_pct"].values()) \
        == pytest.approx(P.idle_split(ctx)["solve"])
    assert idle_in_launch_pct.read(ctx) \
        == pytest.approx(100.0 * 250 / slice_ns)
    assert idle_awaiting_input_pct.read(ctx) \
        == pytest.approx(100.0 * 30 / slice_ns)
    assert found["solves"] == (2 if slice_ns == 20000 else 1)
    assert any("pmt.serve.solve spans" in m and "sum " in m
               for m in ctx["said"])


# ----------------------------------------- nothing to read, and saying so
@pytest.mark.parametrize("metric", CLOSED + SERVED,
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_a_parents_trace_reads_nothing_and_says_so(tmp_path, monkeypatch,
                                                   metric):
    """The program before the names: the ops and spans it had read as
    before, the six new metrics read ``None`` with a line on the log."""
    planes = served(named=False) if metric in SERVED \
        else solved(named=False)
    ctx = ctx_of(tmp_path, monkeypatch, planes,
                 {"iterations_per_solve": 2})
    assert metric.read(ctx) is None
    if metric in OWN:
        assert solver_self_device_ms.read(ctx) == pytest.approx(290 / 1e6)
        assert any("no pmt.solver scope in the trace" in m
                   and "compile cache" in m for m in ctx["said"])
    else:
        assert any("no pmt.solver.launch / collect span" in m
                   for m in ctx["said"])
    if metric in SERVED:
        assert P.idle_split(ctx)["solve"] == pytest.approx(3.9)


@pytest.mark.parametrize("metric", CLOSED + SERVED,
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_a_clock_violation_silences_them(tmp_path, monkeypatch, metric):
    """A first device op well before the span that dispatched it: the
    two clocks cannot be held against each other, the launch span's
    duration included."""
    monkeypatch.setattr(P, "CLOCK_SLACK_NS", 20)
    if metric in SERVED:
        planes = served()
        planes[0][1][0][1].insert(
            0, ("%fusion.0 = f32[] fusion()", 2900, 200, None,
                {"tf_op": BODY + "pmt.MPIBlockDiag.matvec/dot"}))
    else:
        planes = solved(first_op_at=1070)
    ctx = ctx_of(tmp_path, monkeypatch, planes,
                 {"iterations_per_solve": 2})
    assert metric.read(ctx) is None
    assert any("clock violations 1" in m or "clock violations 2" in m
               for m in ctx["said"])
    ctx = ctx_of(tmp_path, monkeypatch,
                 served() if metric in SERVED else solved(),
                 {"iterations_per_solve": 2})
    assert metric.read(ctx) is not None
