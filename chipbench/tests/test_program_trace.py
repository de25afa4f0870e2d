"""Tests of ``program_trace`` and the six readers built on it, on
synthesized traces (``xplane_stats_writer``): the idle attribution
arithmetic, the clock check, the scope split. No chip needed."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import program_trace as P, trace_reduce as T  # noqa: E402
from chipbench.layers import (admit_wait_p50_ms, device_idle_pct,  # noqa: E402
                              idle_in_collect_pct, idle_in_staging_pct,
                              iter_device_ms, operator_device_ms,
                              solver_self_device_ms, stage_host_ms)
from chipbench.tests.xplane_stats_writer import xspace  # noqa: E402

DEV = "/device:TPU:0"
CELL = "some.cell"


def ctx_of(tmp_path, monkeypatch, planes, records):
    """Write ``planes`` where a traced run of ``CELL`` leaves its
    trace, and the ``ctx`` a reader gets for it."""
    d = tmp_path / "trace" / CELL / "plugins" / "profile" / "run"
    d.mkdir(parents=True, exist_ok=True)
    path = d / "t.xplane.pb"
    path.write_bytes(xspace(planes))
    monkeypatch.setattr(P, "OUT", str(tmp_path))
    P.load.cache_clear()
    said = []
    return {"trace": T.load(str(path)), "cell": {"name": CELL},
            "records": records, "log": said.append, "said": said}


def batch(n, t, pack, stage_in, solve, pull, resolve):
    """The dispatcher's spans of batch ``n`` from ``t`` on, each stage
    as long as given."""
    ev, at = [], t
    for name, dur in (("pack", pack), ("stage_in", stage_in),
                      ("solve", solve), ("pull", pull),
                      ("resolve", resolve)):
        ev.append((f"pmt.serve.{name}", at, dur, {"batch": n}))
        at += dur
    return [("pmt.serve.batch", t, at - t,
             {"batch": n, "k": 3, "bucket": 4})] + ev


def served(first_op_at=3200):
    """Two batches on the dispatcher's thread, the device busy inside
    their ``solve`` spans only; slice 1000..21000."""
    ops = [("%while.1 = () while()", 3100, 4800),
           ("%fusion.1 = f32[] fusion()", first_op_at, 5000 - first_op_at),
           ("%fusion.2 = f32[] fusion()", 5200, 2600),
           ("%fusion.1 = f32[] fusion()", 13100, 2900),
           ("%fusion.2 = f32[] fusion()", 16000, 2900)]
    disp = [("pmt.serve.collect", 500, 1500, {"batch": 1})] \
        + batch(1, 2000, 300, 700, 5000, 600, 400) \
        + [("pmt.serve.collect", 9000, 3000, {"batch": 2})] \
        + batch(2, 12000, 400, 600, 6000, 500, 500) \
        + [("pmt.serve.collect", 20000, 2000, {"batch": 3}),
           ("ThreadpoolListener::Record", 100, 50)]
    main = [("cb.slice", 1000, 20000), ("cb.submit", 1500, 100),
            ("pmt.solver.block_cgls", 100, 50, {"batch": 4})]
    return [(DEV, [("XLA Ops", ops),
                   ("XLA Modules", [("jit_f", 3000, 5000)])]),
            ("/host:CPU", [("main", main), ("pylops-serve-dispatch", disp)])]


def test_wire_reader_agrees_with_profile_data(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, served(), {})
    pt = P.for_ctx(ctx)
    assert [(s, e, n) for s, e, n, _ in pt.ops[DEV]] \
        == ctx["trace"].devices[DEV]            # leaves only, same clock
    assert pt.dispatcher_line() == 1
    (s, e, name, line, stats), = pt.spans("serve.batch", 0, 10000)
    assert (s, e, line, stats) == (2000.0, 9000.0, 1,
                                   {"batch": 1, "k": 3, "bucket": 4})
    assert all(h[2].startswith("pmt.") for h in pt.host)
    assert P.for_ctx({"trace": None}) is None


def test_idle_split_sums_to_the_idle_total(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, served(), {})
    split = P.idle_split(ctx)
    # slice 20000; busy 1800 + 2600 + 5800; the four stages are idle
    # throughout, collect covers 1000 + 3000 + 1000 of the slice, and
    # inside solve the device waits 600 + 200
    assert split["total"] == pytest.approx(49.0)
    assert split["staging"] == pytest.approx(20.0)
    assert split["collect"] == pytest.approx(25.0)
    assert split["solve"] == pytest.approx(4.0)
    assert split["elsewhere"] == pytest.approx(0.0)
    assert split["total"] == pytest.approx(device_idle_pct.read(ctx))
    assert idle_in_staging_pct.read(ctx) == pytest.approx(20.0)
    assert idle_in_collect_pct.read(ctx) == pytest.approx(25.0)
    assert any("staging 20.00 + collect 25.00 + solve 4.00" in m
               for m in ctx["said"])
    # batch 1: 300 + 700 + 600 + 400; batch 2: 400 + 600 + 500 + 500
    assert stage_host_ms.read(ctx) == pytest.approx(2000 / 1e6)


def test_a_clock_violation_silences_the_attribution(tmp_path, monkeypatch):
    """A device op that began well before the ``solve`` span it
    belongs to: the two clocks cannot be trusted against each other.
    A lead inside the slack is said and let pass."""
    monkeypatch.setattr(P, "CLOCK_SLACK_NS", 50)
    ctx = ctx_of(tmp_path, monkeypatch, served(first_op_at=2960), {})
    assert P.clock_check(P.for_ctx(ctx), ctx["trace"]) == (0, 40.0)
    assert idle_in_staging_pct.read(ctx) is not None
    assert any("clock violations 0 " in m for m in ctx["said"])
    ctx = ctx_of(tmp_path, monkeypatch, served(first_op_at=2900), {})
    assert P.clock_check(P.for_ctx(ctx), ctx["trace"]) == (1, 0.0)
    assert idle_in_staging_pct.read(ctx) is None
    assert idle_in_collect_pct.read(ctx) is None
    assert any("clock violations 1" in m for m in ctx["said"])
    # host spans alone need no second clock
    assert stage_host_ms.read(ctx) == pytest.approx(2000 / 1e6)


def test_a_parent_without_spans_reads_nothing(tmp_path, monkeypatch):
    planes = served()
    planes[1] = ("/host:CPU", [("main", [("cb.slice", 1000, 20000)])])
    ctx = ctx_of(tmp_path, monkeypatch, planes,
                 {"service": {"wait_p50_s": 1.3}})
    for reader in (stage_host_ms, idle_in_staging_pct, idle_in_collect_pct,
                   admit_wait_p50_ms):
        assert reader.read(ctx) is None
    ctx["records"]["service"]["admit_wait_p50_s"] = 0.25
    assert admit_wait_p50_ms.read(ctx) == pytest.approx(250.0)


def solved(scoped=True):
    """Two solves of two iterations; slice 0..10000."""
    def op(name, at, dur, path):
        return (name, at, dur, None, {"tf_op": path} if scoped else None)

    ops = []
    for t in (0, 5000):
        ops += [("%while.3 = () while()", t + 1200, 3700),
                op("%multiply_reduce_fusion.24 = f32[] fusion()", t + 1300,
                   1000, "jit(f)/while/body/pmt.MPIBlockDiag.matvec/reduce"),
                op("%multiply_reduce_fusion.21 = f32[] fusion()", t + 2300,
                   1000, "jit(f)/while/body/pmt.MPIBlockDiag.rmatvec/reduce"),
                op("%add.7 = f32[] add()", t + 3300, 200,
                   "jit(f)/while/body/add"),
                op("%mul.9 = f32[] multiply()", t + 3500, 200,
                   "jit(f)/while/body/pmt.Outer.matvec/"
                   "pmt.MPIBlockDiag.matvec/mul")]
    host = [("cb.slice", 0, 10000), ("cb.solve", 1000, 4000),
            ("cb.solve", 6000, 3900), ("pmt.solver.cgls", 1100, 3890,
                                       {"op": "MPIBlockDiag", "niter": 2}),
            ("pmt.solver.cgls", 6100, 3790)]
    return [(DEV, [("XLA Ops", ops)]), ("/host:CPU", [("main", host)])]


def test_operator_and_solver_self_time(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, solved(),
                 {"iterations_per_solve": 2})
    assert P.scopes_of("jit(f)/pmt.A.matvec/pmt.B.rmatvec/dot") \
        == ("pmt.A.matvec", "pmt.B.rmatvec")
    split = P.operator_split(ctx)
    # two solves x two iterations; an op counts under its innermost scope
    assert split == {"pmt.MPIBlockDiag.matvec": pytest.approx(600 / 1e6),
                     "pmt.MPIBlockDiag.rmatvec": pytest.approx(500 / 1e6)}
    whole, ops = iter_device_ms.read(ctx), operator_device_ms.read(ctx)
    assert whole == pytest.approx(1200 / 1e6)
    assert ops == pytest.approx(1100 / 1e6)
    assert solver_self_device_ms.read(ctx) == pytest.approx(whole - ops)
    assert any("pmt.MPIBlockDiag.rmatvec 0.001" in m for m in ctx["said"])


def test_an_unnamed_program_is_said_and_left_out(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, solved(scoped=False),
                 {"iterations_per_solve": 2})
    assert operator_device_ms.read(ctx) is None
    assert solver_self_device_ms.read(ctx) is None
    assert any("no pmt scope in the trace" in m and "compile cache" in m
               for m in ctx["said"])
