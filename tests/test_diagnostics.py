"""Diagnostics subsystem (round 9): span tracer, cost model/roofline,
in-loop telemetry, deadline runner.

Covers the ISSUE-4 checklist: span nesting/ordering, JSONL schema
round-trip, cost-model FLOPs/bytes vs hand counts for
MatrixMult(block|summa)/BlockDiag/FFT transpose, the
telemetry-vs-unfused residual-history oracle, the HLO zero-callback
pin with ``PYLOPS_MPI_TPU_TRACE=off``, and the central stage-budget
table + deadline-aware runner.
"""

import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.diagnostics import (trace, telemetry, costmodel,
                                        profiler)
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.utils import hlo

NDEV = len(jax.devices())


@pytest.fixture(autouse=True)
def _clean_trace(monkeypatch):
    """Every test starts with empty buffers and tracing OFF (the
    shipping default); tests opt in per-case via monkeypatch."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_TRACE", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_TELEMETRY", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_TRACE_FILE", raising=False)
    trace.clear_events()
    telemetry.clear_history()
    yield
    trace.clear_events()
    telemetry.clear_history()


def _mk_blockdiag(rng, nblk=None, n=16):
    nblk = NDEV if nblk is None else nblk
    blocks = [rng.standard_normal((n, n)).astype(np.float32)
              + 4 * np.eye(n, dtype=np.float32) for _ in range(nblk)]
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                           for b in blocks])
    x = rng.standard_normal(nblk * n).astype(np.float32)
    y = pmt.DistributedArray.to_dist(
        np.concatenate([b @ x[i * n:(i + 1) * n]
                        for i, b in enumerate(blocks)]))
    return Op, y, x


# ------------------------------------------------------------------ tracer
def test_trace_off_by_default_records_nothing():
    assert trace.trace_mode() == "off"
    with trace.span("should.not.record", foo=1):
        trace.event("also.not.recorded")
        trace.counter("nor.this", {"v": 1.0})
    assert trace.get_events() == []


def test_unknown_trace_mode_falls_back_to_off(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "fulll")
    assert trace.trace_mode() == "off"


def test_span_nesting_and_ordering(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    with trace.span("outer", tag="a"):
        with trace.span("inner1"):
            pass
        with trace.span("inner2"):
            with trace.span("leaf"):
                pass
    with trace.span("second_root"):
        pass
    events = trace.get_events()
    # recorded at exit: children precede parents in the buffer
    names = [e["name"] for e in events]
    assert names == ["inner1", "leaf", "inner2", "outer", "second_root"]
    # depth/parent tags
    by_name = {e["name"]: e for e in events}
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["inner1"]["args"] == {"depth": 1, "parent": "outer"}
    assert by_name["leaf"]["args"]["parent"] == "inner2"
    # tree reconstruction: chronological roots, nested children
    roots = trace.span_tree(events)
    assert [r["name"] for r in roots] == ["outer", "second_root"]
    outer = roots[0]
    assert [c["name"] for c in outer["children"]] == ["inner1", "inner2"]
    assert [c["name"] for c in outer["children"][1]["children"]] == \
        ["leaf"]
    # timestamps are monotone and spans contain their children
    assert outer["ts"] <= outer["children"][0]["ts"]
    assert outer["dur"] >= outer["children"][1]["dur"]


def test_jsonl_schema_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    with trace.span("a.span", cat="operator", shape=(4, 4),
                    dtype=np.float32):
        trace.event("an.event", cat="fallback", detail="note")
    trace.counter("a.counter", {"resid": 0.5})
    path = tmp_path / "trace.jsonl"
    n = trace.dump(str(path))
    lines = path.read_text().strip().splitlines()
    assert n == len(lines) == 3
    required = {"X": {"name", "ph", "ts", "dur", "pid", "tid"},
                "i": {"name", "ph", "ts", "pid", "tid"},
                "C": {"name", "ph", "ts", "pid", "tid"}}
    phs = []
    for line in lines:
        ev = json.loads(line)  # every line is one valid JSON object
        phs.append(ev["ph"])
        assert required[ev["ph"]] <= set(ev)
        assert json.loads(json.dumps(ev)) == ev  # round-trips
    assert sorted(phs) == ["C", "X", "i"]
    # tags were JSON-sanitized (tuple -> list, dtype -> str)
    span_ev = json.loads(lines[1]) if phs[1] == "X" else \
        next(json.loads(l) for l in lines if json.loads(l)["ph"] == "X")
    assert span_ev["args"]["shape"] == [4, 4]
    assert isinstance(span_ev["args"]["dtype"], str)
    # chrome format: a single JSON array Perfetto can open
    cpath = tmp_path / "trace.json"
    trace.dump(str(cpath), fmt="chrome")
    assert isinstance(json.load(open(cpath)), list)


def test_span_tags_never_crash_on_weird_values(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    with trace.span("weird", mesh=object(), arr=np.arange(3),
                    nested={"t": (1, np.float64(2.0))}):
        pass
    ev = trace.get_events()[-1]
    json.dumps(ev)  # everything serializable


def test_mid_span_tag(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    with trace.span("with.late.tag") as sp:
        sp.tag(resolved_chunks=3)
    assert trace.get_events()[-1]["args"]["resolved_chunks"] == 3


# --------------------------------------------------------- wired-in spans
def test_operator_apply_opens_tagged_span(monkeypatch, rng):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    Op, y, _ = _mk_blockdiag(rng)
    Op.matvec(pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32)))
    ops = [e for e in trace.get_events() if e.get("cat") == "operator"]
    assert any(e["name"] == "MPIBlockDiag.matvec" for e in ops)
    ev = next(e for e in ops if e["name"] == "MPIBlockDiag.matvec")
    assert ev["args"]["shape"] == list(Op.shape)
    assert "mesh_axes" in ev["args"]


def test_summa_schedule_select_and_collective_spans(monkeypatch, rng):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    A = rng.standard_normal((32, 32)).astype(np.float32)
    Op = pmt.MPIMatrixMult(A, M=8, kind="summa", overlap=True)
    x = pmt.DistributedArray.to_dist(
        rng.standard_normal(32 * 8).astype(np.float32))
    Op.matvec(x)
    events = trace.get_events()
    sel = [e for e in events if e["name"] == "summa.schedule_select"]
    assert len(sel) == 1
    assert sel[0]["args"]["schedule"] in ("gather", "stat_a")
    assert sel[0]["args"]["vol_gather"] > 0
    assert sel[0]["args"]["vol_stat_a"] > 0
    # the gather schedule's overlapped forward goes through ring_pass
    Op2 = pmt.MPIMatrixMult(A, M=8, kind="summa", overlap=True,
                            schedule="gather")
    if Op2.grid[1] > 1:  # ring kernels only engage on a >1-wide 'c' axis
        trace.clear_events()
        Op2.matvec(x)
        rings = [e for e in trace.get_events()
                 if e["name"] == "collective.ring_pass"]
        assert rings and rings[0]["args"]["n_shards"] == Op2.grid[1]


def test_resolve_chunks_fallback_event(monkeypatch):
    from pylops_mpi_tpu.parallel.collectives import resolve_chunks
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    got = resolve_chunks(16, 8, 64, where="unit-test")
    assert got == 2  # capped at width // n_shards
    evs = [e for e in trace.get_events() if e.get("cat") == "fallback"]
    assert len(evs) == 1
    assert evs[0]["name"] == "collective.resolve_chunks_fallback"
    assert evs[0]["args"] == {"where": "unit-test", "requested": 64,
                              "width": 16, "n_shards": 8, "resolved": 2}
    # a fitting request emits nothing
    trace.clear_events()
    assert resolve_chunks(64, 8, 4, where="unit-test") == 4
    assert trace.get_events() == []


# --------------------------------------------------------------- costmodel
def test_summa_comm_volume_matches_inline_formula():
    for (N, K, M, grid) in [(32, 32, 8, (2, 4)), (100, 60, 7, (4, 2)),
                            (16, 16, 16, (1, 1))]:
        pr, pc = grid
        Np = pr * math.ceil(N / pr)
        Kp_r = pr * math.ceil(K / pr)
        Kp_c = pc * math.ceil(K / pc)
        Mp = pc * math.ceil(M / pc)
        want_gather = ((Np // pr) * Kp_c * (pc - 1) / pc
                       + Kp_r * (Mp // pc) * (pr - 1) / pr)
        want_stat_a = (Kp_r * (Mp // pc) * (pr - 1) / pr
                       + Kp_r * Mp * (pc - 1) / pc
                       + (Np // pr) * Mp * (pc - 1) / pc)
        vols = costmodel.summa_comm_volume(N, K, M, grid)
        assert vols["gather"] == want_gather
        assert vols["stat_a"] == want_stat_a


def test_cost_block_matmul_hand_count(rng):
    N = K = 32
    M = 8
    A = rng.standard_normal((N, K)).astype(np.float32)
    Op = pmt.MPIMatrixMult(A, M=M, kind="block")
    P = NDEV
    fwd = costmodel.estimate(Op, "forward")
    assert fwd.flops == 2.0 * N * K * M / P
    assert fwd.hbm_bytes == N * K * 4 / P + (K * M + N * M / P) * 4
    assert fwd.ici_bytes == 0.0
    adj = costmodel.estimate(Op, "adjoint")
    assert adj.flops == 2.0 * N * K * M / P
    assert adj.ici_bytes == K * M * 4 * 2.0 * (P - 1) / P


def test_cost_summa_matmul_hand_count(rng):
    N = K = 32
    M = 8
    A = rng.standard_normal((N, K)).astype(np.float32)
    Op = pmt.MPIMatrixMult(A, M=M, kind="summa")
    pr, pc = Op.grid
    P = pr * pc
    fwd = costmodel.estimate(Op, "forward")
    assert fwd.flops == 2.0 * Op.Np * Op.Kp_c * Op.Mp / P
    vols = costmodel.summa_comm_volume(N, K, M, Op.grid)
    if Op.schedule == "stat_a":
        assert fwd.ici_bytes == vols["stat_a"] * 4
    else:
        a_term = (Op.Np // pr) * Op.Kp_c * (pc - 1) / pc
        assert fwd.ici_bytes == a_term * 4 + (vols["gather"] - a_term) * 4
    adj = costmodel.estimate(Op, "adjoint")
    assert adj.ici_bytes == vols["adjoint"] * 4
    # the auto-select picked the cheaper schedule per the shared model
    want = "stat_a" if vols["stat_a"] < vols["gather"] else "gather"
    assert Op.schedule == want


def test_cost_blockdiag_hand_count(rng):
    n = 16
    Op, _, _ = _mk_blockdiag(rng, n=n)
    nblk = NDEV
    c = costmodel.estimate(Op, "forward")
    assert c.flops == 2.0 * nblk * n * n / NDEV
    assert c.hbm_bytes == (nblk * n * n * 4
                           + (Op.shape[0] + Op.shape[1]) * 4) / NDEV
    assert c.ici_bytes == 0.0


def test_cost_fft_pencil_transpose_hand_count():
    shape = (64, 64)
    P = 8
    c = costmodel.pencil_transpose_cost(shape, P, itemsize=8,
                                        n_transposes=2)
    local = 64 * 64 * 8 / P
    assert c.ici_bytes == local * (P - 1) / P * 2
    assert c.hbm_bytes == 2 * local * 2
    # one device: no ICI term at all
    c1 = costmodel.pencil_transpose_cost(shape, 1, itemsize=8)
    assert c1.ici_bytes == 0.0


def test_cost_wrappers_compose(rng):
    Op, _, _ = _mk_blockdiag(rng)
    base_f = costmodel.estimate(Op, "forward")
    base_a = costmodel.estimate(Op, "adjoint")
    assert costmodel.estimate(Op.H, "forward").flops == base_a.flops
    assert costmodel.estimate(2.0 * Op, "forward").flops == base_f.flops
    both = costmodel.estimate(Op.H @ Op, "forward")
    assert both.flops == base_f.flops + base_a.flops


def test_estimate_unknown_operator_returns_none():
    class Weird:
        pass
    assert costmodel.estimate(Weird()) is None


def test_roofline_bound_and_prediction():
    cost = costmodel.OpCost(flops=1e12, hbm_bytes=1e9, ici_bytes=1e8)
    peaks = {"flops": 275e12, "hbm_gbps": 1228.0, "ici_gbps": 300.0}
    rl = costmodel.roofline(cost, peaks, n_dev=4)
    t_c, t_h, t_i = 1e12 / 275e12, 1e9 / 1228e9, 1e8 / 300e9
    assert rl["bound"] == "compute"
    assert rl["predicted_s"] == pytest.approx(max(t_c, t_h, t_i))
    # unknown peaks -> no roofline, never a wrong one
    rl0 = costmodel.roofline(cost, {"flops": None, "hbm_gbps": None})
    assert rl0["predicted_s"] is None and rl0["bound"] is None
    # hbm-bound case
    rl_h = costmodel.roofline(
        costmodel.OpCost(flops=1e9, hbm_bytes=1e9), peaks)
    assert rl_h["bound"] == "hbm"


def test_peak_tables_match_bench():
    """The package's peak table and the benchmark's
    ``chipbench/peaks.json`` state the same v5e figures."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "peaks.json")
    with open(path) as f:
        v5e = json.load(f)["TPU v5 lite"]
    bf16 = v5e["bf16_flops_per_s"]
    assert costmodel.peak_flops("TPU v5 lite") == bf16
    assert costmodel.peak_flops("TPU v5 lite", "f32_highest") == \
        bf16 / v5e["f32_passes"]
    assert costmodel.peak_hbm_gbps("TPU v5 lite") * 1e9 == \
        v5e["hbm_bytes_per_s"]
    assert costmodel.peak_flops("unknown chip") is None
    assert costmodel.peak_flops("v4", "f32_highest") == 275e12 / 6


# --------------------------------------------------------------- telemetry
def test_telemetry_off_by_default():
    assert not telemetry.telemetry_enabled()
    assert telemetry.telemetry_signature() == ("telemetry", False)


def test_telemetry_gating(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    assert not telemetry.telemetry_enabled()  # spans mode is host-only
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    assert telemetry.telemetry_enabled()
    monkeypatch.setenv("PYLOPS_MPI_TPU_TELEMETRY", "off")
    assert not telemetry.telemetry_enabled()  # explicit off wins
    monkeypatch.delenv("PYLOPS_MPI_TPU_TRACE")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TELEMETRY", "on")
    assert telemetry.telemetry_enabled()  # explicit on wins too


def test_fused_cgls_telemetry_matches_unfused_history(monkeypatch, rng):
    """The oracle: the per-iteration residuals captured from INSIDE the
    fused while_loop equal the on-device cost history the solver
    returns (same computation, observed two ways)."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    Op, y, _ = _mk_blockdiag(rng)
    niter = 8
    out = pmt.cgls(Op, y, niter=niter, tol=0.0)
    cost = out[5]
    hist = telemetry.history("cgls")
    assert len(hist) == niter
    assert [h["iiter"] for h in hist] == list(range(1, niter + 1))
    got = np.asarray([h["resid"] for h in hist])
    np.testing.assert_allclose(got, np.asarray(cost)[1:], rtol=1e-6)


def test_fused_cg_telemetry(monkeypatch, rng):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    Op, y, _ = _mk_blockdiag(rng)
    niter = 5
    x, iiter, cost = pmt.cg(Op, y, niter=niter, tol=0.0)
    hist = telemetry.history("cg")
    assert len(hist) == niter
    got = np.asarray([h["resid"] for h in hist])
    np.testing.assert_allclose(got, np.asarray(cost)[1:], rtol=1e-6)


def test_fista_telemetry(monkeypatch, rng):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    Op, y, _ = _mk_blockdiag(rng)
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))
    niter = 6
    x, iiter, cost = pmt.fista(Op, y, x0=x0, niter=niter, eps=1e-4)
    hist = telemetry.history("fista")
    assert len(hist) == iiter
    got = np.asarray([h["cost"] for h in hist])
    np.testing.assert_allclose(got, np.asarray(cost), rtol=1e-5)


def test_class_api_step_records_telemetry(monkeypatch, rng):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    Op, y, _ = _mk_blockdiag(rng)
    out = pmt.cgls(Op, y, niter=4, tol=0.0, fused=False)
    assert len(telemetry.history("cgls")) == 4


# --------------------------------------------------- the zero-callback pin
def test_hlo_zero_host_callbacks_when_trace_off(monkeypatch, rng):
    """Acceptance: with PYLOPS_MPI_TPU_TRACE=off (default), the fused
    solver programs contain ZERO host callbacks — the donated/fused
    hot path is untouched by the diagnostics layer."""
    from pylops_mpi_tpu.solvers.basic import _cgls_fused, _cg_fused
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "off")
    Op, y, _ = _mk_blockdiag(rng)
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))
    hlo.assert_no_host_callbacks(
        lambda y, x, damp, tol: _cgls_fused(Op, y, x, damp, tol,
                                            niter=4), y, x0, 0.0, 0.0)
    hlo.assert_no_host_callbacks(
        lambda y, x, tol: _cg_fused(Op, y, x, tol, niter=4), y, x0, 0.0)


def test_hlo_callback_pin_catches_telemetry_on(monkeypatch, rng):
    from pylops_mpi_tpu.solvers.basic import _cgls_fused
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    Op, y, _ = _mk_blockdiag(rng)
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))
    n = hlo.count_host_callbacks(
        lambda y, x, damp, tol: _cgls_fused(Op, y, x, damp, tol,
                                            niter=4), y, x0, 0.0, 0.0)
    assert n >= 1
    with pytest.raises(AssertionError, match="host-callback"):
        hlo.assert_no_host_callbacks(
            lambda y, x, damp, tol: _cgls_fused(Op, y, x, damp, tol,
                                                niter=4),
            y, x0, 0.0, 0.0)


def test_spans_mode_leaves_hlo_bit_identical(monkeypatch, rng):
    """`spans` tracing is host-side only: the compiled program, less
    its provenance (source lines, scope names), is IDENTICAL to the
    untraced build (only `full`/telemetry may change programs, and
    those retrace via the cache key)."""
    from pylops_mpi_tpu.solvers.basic import _cgls_fused
    Op, y, _ = _mk_blockdiag(rng)
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))

    def compile_text():
        return hlo.compiled_hlo(
            lambda y, x, damp, tol: _cgls_fused(Op, y, x, damp, tol,
                                                niter=3),
            y, x0, 0.0, 0.0)

    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "off")
    off_text = compile_text()
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    spans_text = compile_text()
    assert hlo.strip_provenance(off_text) \
        == hlo.strip_provenance(spans_text)


def test_fused_cache_keys_on_telemetry(monkeypatch, rng):
    """Flipping telemetry retraces rather than reusing an executable
    with the wrong callback contract."""
    from pylops_mpi_tpu.solvers.basic import _FUSED_CACHE
    Op, y, _ = _mk_blockdiag(rng)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "off")
    pmt.cgls(Op, y, niter=3, tol=0.0)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    pmt.cgls(Op, y, niter=3, tol=0.0)
    keys = [k for k in _FUSED_CACHE if k and k[0] == id(Op)]
    assert len(keys) == 2  # one per telemetry state
    assert len(telemetry.history("cgls")) == 3  # only the full-mode run


# ----------------------------------------------- acceptance: CGLS artifact
def test_cpu_sim_cgls_emits_full_chrome_trace(monkeypatch, tmp_path,
                                              rng):
    """Acceptance criterion: one CPU-sim CGLS run with tracing on
    emits a valid Chrome-trace JSONL containing operator, collective
    and per-iteration telemetry events."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    A = rng.standard_normal((32, 32)).astype(np.float32) \
        + 4 * np.eye(32, dtype=np.float32)
    Op = pmt.MPIMatrixMult(A, M=8, kind="summa", overlap=True)
    x = pmt.DistributedArray.to_dist(
        rng.standard_normal(32 * 8).astype(np.float32))
    y = Op.matvec(x)
    pmt.cgls(Op, y, niter=5, tol=0.0)
    path = tmp_path / "cgls_trace.jsonl"
    n = trace.dump(str(path))
    assert n > 0
    cats = set()
    for line in path.read_text().strip().splitlines():
        ev = json.loads(line)
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        cats.add(ev.get("cat"))
    assert {"operator", "collective", "telemetry", "solver"} <= cats


# ------------------------------------------- budgets and deadline runner
def test_stage_budget_table_and_overrides(monkeypatch):
    assert profiler.stage_budget("tune") == 600
    assert profiler.stage_budget("serve_batch") == 120
    monkeypatch.setenv("PROBE_TUNE_TIMEOUT", "123")
    assert profiler.stage_budget("tune") == 123
    monkeypatch.setenv("PROBE_TUNE_TIMEOUT", "not-a-number")
    assert profiler.stage_budget("tune") == 600
    monkeypatch.setenv("PROBE_SERVE_BATCH_TIMEOUT", "77")
    assert profiler.stage_budget("serve_batch") == 77
    with pytest.raises(KeyError):
        profiler.stage_budget("no_such_stage")


def test_budget_table_is_one_flat_column():
    """One budget per stage: no second column for a CPU rehearsal of a
    harvest ladder that no longer exists."""
    assert profiler.STAGE_BUDGETS
    assert all(isinstance(v, int) for v in profiler.STAGE_BUDGETS.values())
    from pylops_mpi_tpu.tuning.search import tune_budget_s
    assert tune_budget_s() == profiler.stage_budget("tune")


def test_deadline_runner_runs_and_records():
    r = profiler.DeadlineRunner(deadline_ts=None)
    rec = r.run("ok_stage", lambda t: ({"value": 1, "t": t}, None),
                budget_s=50)
    assert rec["ok"] and not rec["skipped"]
    assert rec["effective_timeout_s"] == 50
    assert rec["result"]["t"] == 50
    assert not rec["banked_partial"]


def test_deadline_runner_caps_timeout_at_remaining_window():
    import time as _t
    r = profiler.DeadlineRunner(deadline_ts=_t.time() + 40)
    rec = r.run("capped", lambda t: ({"t": t}, None), budget_s=500)
    assert rec["effective_timeout_s"] <= 40


def test_deadline_runner_banks_partial_on_budget_kill():
    """A stage killed at budget whose salvaged line carries the
    `salvaged_after_timeout` stamp is recorded as a banked partial —
    and the runner keeps going (window yielded, not eaten)."""
    import time as _t

    def slow_stage(t):
        _t.sleep(min(t, 1.0))
        return {"salvaged_after_timeout": t, "value": 7}, None

    r = profiler.DeadlineRunner(deadline_ts=None)
    rec = r.run("killed", slow_stage, budget_s=1)
    assert rec["banked_partial"]
    assert rec["hit_budget"]
    rec2 = r.run("next", lambda t: ({"fine": True}, None), budget_s=10)
    assert rec2["ok"]
    rep = r.report()
    assert rep["banked_partials"] == ["killed"]
    assert rep["skipped"] == []


def test_deadline_runner_skips_exhausted_window():
    import time as _t
    r = profiler.DeadlineRunner(deadline_ts=_t.time() + 2,
                                min_stage_s=30)
    rec = r.run("wont_fit", lambda t: ({"x": 1}, None), budget_s=600)
    assert rec["skipped"] and not rec["ok"]
    assert "remaining" in rec["reason"]
    assert r.report()["skipped"] == ["wont_fit"]


def test_deadline_runner_survives_raising_stage():
    r = profiler.DeadlineRunner()
    rec = r.run("boom", lambda t: 1 / 0, budget_s=5)
    assert not rec["ok"] and "stage raised" in rec["error"]


# ------------------------------------- the profiler's clock (sink 1)
def _profiled(tmp_path, body):
    """Run ``body()`` under a ``jax.profiler`` session on the CPU and
    return the ``pmt.*`` host events per thread line, in start order,
    as ``(name, start_ns, end_ns, stats)``."""
    import glob
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            ev = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("pmt."))
            if ev:
                lines.append([(n, s, e, st) for s, e, n, st in ev])
    return lines


def test_spans_land_on_the_profilers_clock_with_tracing_off(tmp_path, rng):
    """With ``PYLOPS_MPI_TPU_TRACE`` unset, three requests through a
    tiny daemon and one ``pmt.cgls`` leave the dispatcher's stage
    spans and the solver's span in the profiler's own trace."""
    import time
    from pylops_mpi_tpu.serving import FamilySpec, SolveDaemon, WarmPool
    Op, y, _ = _mk_blockdiag(rng)
    pool = WarmPool(buckets=(1, 2, 4))
    pool.register(FamilySpec(name="f", operator=Op, solver="cgls",
                             niter=3, tol=0.0))
    daemon = SolveDaemon(pool)
    daemon.start(prewarm=True)
    pmt.cgls(Op, y, niter=3, tol=0.0)
    cols = rng.standard_normal((3, Op.shape[0])).astype(np.float32)

    def body():
        time.sleep(0.12)        # a poll of collect starts in the session
        for t in [daemon.submit("f", c) for c in cols]:
            t.wait(timeout=120)
        pmt.cgls(Op, y, niter=3, tol=0.0)

    try:
        lines = _profiled(tmp_path, body)
    finally:
        daemon.drain(timeout=30)
    assert trace.get_events() == []          # the ring buffer stayed off
    disp, = [ln for ln in lines
             if any(n == "pmt.serve.batch" for n, *_ in ln)]
    (_, b0, b1, bstats), = [ev for ev in disp if ev[0] == "pmt.serve.batch"]
    assert bstats["k"] == 3 and bstats["bucket"] == 4
    inside = [ev for ev in disp if ev[0].startswith("pmt.serve.")
              and b0 <= ev[1] and ev[2] <= b1 and ev[0] != "pmt.serve.batch"]
    assert [n for n, *_ in inside] == [
        "pmt.serve." + s
        for s in ("pack", "stage_in", "solve", "pull", "resolve")]
    assert {st["batch"] for *_, st in inside} == {bstats["batch"]}
    before = [ev for ev in disp if ev[0] == "pmt.serve.collect"
              and ev[2] <= b0]
    assert before and before[-1][3]["batch"] == bstats["batch"]
    assert any(n == "pmt.solver.cgls" for ln in lines if ln is not disp
               for n, *_ in ln)


def test_named_scopes_are_provenance_only(monkeypatch, rng):
    """The compiled fused CGLS carries the operator scopes in
    ``op_name``, and less its provenance it is the program built with
    the scopes patched out."""
    import contextlib
    from pylops_mpi_tpu.solvers.basic import _cgls_fused
    Op, y, _ = _mk_blockdiag(rng)
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))

    def compile_text():
        return hlo.compiled_hlo(
            lambda y, x, damp, tol: _cgls_fused(Op, y, x, damp, tol,
                                                niter=3),
            y, x0, 0.0, 0.0)

    named = compile_text()
    for scope in ("pmt.MPIBlockDiag.matvec", "pmt.MPIBlockDiag.rmatvec"):
        assert any(scope in m for m in
                   re.findall(r'op_name="([^"]*)"', named)), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compile_text()
    assert "pmt." not in bare
    assert hlo.strip_provenance(named) == hlo.strip_provenance(bare)


def test_annotation_takes_int_and_str_tags_only(monkeypatch):
    """Outside a jax trace the span is a ``TraceAnnotation`` under the
    ``pmt.`` prefix; tags that are neither ``int`` nor ``str`` are
    dropped, not stringified."""
    seen = []

    class Recorder:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with trace.span("serve.batch", cat="serving", batch=7, family="f",
                    shape=(4, 4), tol=0.5, forced=True, dtype=np.float32):
        pass
    assert seen == [("pmt.serve.batch", {"batch": 7, "family": "f"})]


def test_one_sweep_path_is_scoped(rng):
    """``MPIBlockDiag.normal_matvec`` is a site the operator wiring
    does not reach: the fused kernel's ops carry its scope."""
    from pylops_mpi_tpu.solvers.basic import _cgls_fused_normal
    Op, y, _ = _mk_blockdiag(rng)
    if not Op.has_fused_normal:
        pytest.skip("no fused normal kernel on this backend")
    x0 = pmt.DistributedArray.to_dist(
        np.zeros(Op.shape[1], dtype=np.float32))
    text = hlo.compiled_hlo(
        lambda y, x, damp, tol: _cgls_fused_normal(Op, y, x, damp, tol,
                                                   niter=3),
        y, x0, 0.0, 0.0)
    assert any("pmt.MPIBlockDiag.normal_matvec" in m
               for m in re.findall(r'op_name="([^"]*)"', text))


def test_off_mode_installs_no_flush_handler(monkeypatch, tmp_path):
    """``off`` keeps its meaning for the ring buffer: no event, and no
    exit-flush handler even with a trace file named."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE_FILE", str(tmp_path / "t"))
    monkeypatch.setattr(trace, "_atexit_registered", False)
    with trace.span("quiet", cat="serving", batch=1) as sp:
        sp.tag(later=2)
        with trace.op_span(pmt.MPIBlockDiag.__new__(pmt.MPIBlockDiag),
                           "matvec"):
            trace.event("nor.this")
    assert trace.get_events() == []
    assert trace._OPEN == {}
    assert trace._atexit_registered is False


# ------------------------------------------------------------- roofline
def test_bench_rows_carry_roofline_columns(rng):
    """A two-sweep block matmul's cost on CPU-sim peaks (no flop peak,
    an assumed stream bandwidth) places on the roofline as HBM-bound
    with a positive predicted time."""
    from pylops_mpi_tpu.diagnostics.costmodel import OpCost, roofline
    nblk, nblock, itemsize, sweeps = 8, 256, 4, 2
    cost = OpCost(flops=4.0 * nblock * nblock * nblk / NDEV,
                  hbm_bytes=sweeps * nblock * nblock * nblk * itemsize
                  / NDEV)
    rl = roofline(cost, {"flops": None, "hbm_gbps": 30.0 / NDEV,
                         "ici_gbps": None}, n_dev=NDEV)
    assert rl["bound"] == "hbm"
    assert rl["predicted_s"] > 0


# ----------------------------------------- post-mortem trace flush
# (ISSUE 8 satellite) trace.py is stdlib-only, so subprocesses load it
# by file path — jax-free, milliseconds per case — and die in various
# ways while a span is open; the JSONL artifact must survive.

_TRACE_PY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "pylops_mpi_tpu", "diagnostics",
    "trace.py")

_FLUSH_PRELUDE = f"""
import importlib.util, os, signal, sys, time
spec = importlib.util.spec_from_file_location("trace_mod", {_TRACE_PY!r})
trace = importlib.util.module_from_spec(spec)
spec.loader.exec_module(trace)
"""


def _run_flush_child(body, env_extra, timeout=60):
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYLOPS_MPI_TPU_TRACE")}
    env.update(env_extra)
    return subprocess.Popen([sys.executable, "-u", "-c",
                             _FLUSH_PRELUDE + body],
                            env=env, stdout=subprocess.PIPE, text=True)


def test_trace_flush_on_sigterm(tmp_path):
    """A worker SIGTERMed mid-span (the supervisor's polite kill)
    leaves a parseable JSONL with a ph="B" record naming the phase it
    died in, and still exits with the honest 'killed by SIGTERM'."""
    import signal
    out = str(tmp_path / "post.jsonl")
    body = """
s = trace.span("solve.epoch", solver="cgls").__enter__()
trace.event("worker.ready")
print("READY", flush=True)
time.sleep(60)
"""
    p = _run_flush_child(body, {"PYLOPS_MPI_TPU_TRACE": "spans",
                                "PYLOPS_MPI_TPU_TRACE_FILE": out})
    assert p.stdout.readline().strip() == "READY"
    p.send_signal(signal.SIGTERM)
    assert p.wait(timeout=60) == -signal.SIGTERM
    with open(out) as f:
        events = [json.loads(line) for line in f if line.strip()]
    opens = [e for e in events if e.get("ph") == "B"]
    assert [e["name"] for e in opens] == ["solve.epoch"]
    assert opens[0]["args"]["open"] is True
    assert any(e["name"] == "worker.ready" for e in events)


def test_trace_flush_on_atexit_open_span(tmp_path):
    """A clean interpreter exit with a span still open (sys.exit from
    inside a phase) flushes via atexit with the open span marked."""
    out = str(tmp_path / "exit.jsonl")
    body = """
with trace.span("outer"):
    pass
trace.span("checkpoint.save").__enter__()
sys.exit(0)
"""
    p = _run_flush_child(body, {"PYLOPS_MPI_TPU_TRACE": "spans",
                                "PYLOPS_MPI_TPU_TRACE_FILE": out})
    assert p.wait(timeout=60) == 0
    with open(out) as f:
        events = [json.loads(line) for line in f if line.strip()]
    assert any(e["name"] == "outer" and e.get("ph") == "X"
               for e in events)
    assert any(e["name"] == "checkpoint.save" and e.get("ph") == "B"
               for e in events)


def test_trace_no_handlers_without_trace_file(tmp_path):
    """Library-quiet pin: without PYLOPS_MPI_TPU_TRACE_FILE, tracing
    must not install a SIGTERM handler (a host application's signal
    handling is not ours to take over) and writes no file."""
    out = str(tmp_path / "none.jsonl")
    body = """
with trace.span("work"):
    pass
h = signal.getsignal(signal.SIGTERM)
print("DFL" if h is signal.SIG_DFL else "HOOKED", flush=True)
"""
    p = _run_flush_child(body, {"PYLOPS_MPI_TPU_TRACE": "spans"})
    assert p.stdout.readline().strip() == "DFL"
    assert p.wait(timeout=60) == 0
    assert not os.path.exists(out)
