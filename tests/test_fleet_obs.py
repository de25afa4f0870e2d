"""Fleet observability suite (ISSUE 10): the metrics registry (zero-
cost off, HLO pins, heartbeat embedding, atomic snapshots), cross-
worker trace aggregation (clock alignment, per-collective skew +
straggler attribution, killed-worker hardening), the diagnostics CLI and
the supervisor's ``job_report.json``.

The quick tests drive synthetic traces and jax-free ``python -c``
workers; the real 2-process supervised smoke lives in the
``slow``-marked acceptance test (``tests/fleet_obs_worker.py``)."""

import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.diagnostics import aggregate, metrics, trace
from pylops_mpi_tpu.diagnostics.profiler import stage_budget
from pylops_mpi_tpu.resilience import elastic, supervisor
from pylops_mpi_tpu.resilience.elastic import HeartbeatWriter, read_heartbeat
from pylops_mpi_tpu.resilience.supervisor import launch_job
from pylops_mpi_tpu.solvers.basic import _cg_fused, _cgls_fused
from pylops_mpi_tpu.utils import hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRUB_ENV = ("PYLOPS_MPI_TPU_COORDINATOR", "PYLOPS_MPI_TPU_NUM_PROCESSES",
              "PYLOPS_MPI_TPU_PROCESS_ID", "PYLOPS_MPI_TPU_ATTEMPT",
              "PYLOPS_MPI_TPU_HEARTBEAT_FILE", "PYLOPS_MPI_TPU_HEARTBEAT",
              "PYLOPS_MPI_TPU_WATCHDOG", "PYLOPS_MPI_TPU_METRICS",
              "PYLOPS_MPI_TPU_METRICS_FILE",
              "PYLOPS_MPI_TPU_METRICS_INTERVAL", "PYLOPS_MPI_TPU_TRACE",
              "PYLOPS_MPI_TPU_TRACE_FILE")


@pytest.fixture(autouse=True)
def _clean_obs_env(monkeypatch):
    """No inherited supervisor/metrics/trace contract, and a fresh
    registry + ring buffer per test."""
    for name in _SCRUB_ENV:
        monkeypatch.delenv(name, raising=False)
    elastic.stop_heartbeat()
    metrics.clear_metrics()
    trace.clear_events()
    yield
    elastic.stop_heartbeat()
    metrics.clear_metrics()
    trace.clear_events()


# ------------------------------------------------------ metrics registry
def test_metrics_off_by_default_records_nothing():
    assert metrics.metrics_mode() == "off"
    assert not metrics.metrics_enabled()
    metrics.inc("solver.cg.solves")
    metrics.observe("w", 1.0)
    metrics.set_gauge("g", 2.0)
    with metrics.timer("stage"):
        pass
    snap = metrics.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}


def test_metrics_registry_counts(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.inc("solver.cg.solves")
    metrics.inc("solver.cg.iterations", 10)
    metrics.inc("solver.cg.iterations", 5)
    metrics.set_gauge("world", 2)
    metrics.observe("wall", 0.5)
    metrics.observe("wall", 1.5)
    with metrics.timer("stage"):
        pass
    snap = metrics.snapshot()
    assert snap["schema"] == metrics.SNAPSHOT_SCHEMA
    assert snap["counters"]["solver.cg.solves"] == 1
    assert snap["counters"]["solver.cg.iterations"] == 15
    assert snap["gauges"]["world"] == 2
    h = snap["histograms"]["wall"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 2.0, 0.5, 1.5)
    assert snap["histograms"]["stage.wall_s"]["count"] == 1


def test_metrics_snapshot_atomic_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.inc("x", 3)
    path = str(tmp_path / "m.json")
    assert metrics.write_snapshot(path) == path
    assert not [p for p in os.listdir(tmp_path) if p != "m.json"], \
        "temp staging file leaked"
    back = metrics.read_snapshot(path)
    assert back["counters"]["x"] == 3
    # corruption degrades to None, never an exception
    bad = tmp_path / "bad.json"
    bad.write_text("{ not json")
    assert metrics.read_snapshot(str(bad)) is None
    assert metrics.read_snapshot(str(tmp_path / "missing.json")) is None
    (tmp_path / "noschema.json").write_text(json.dumps({"pid": 1}))
    assert metrics.read_snapshot(str(tmp_path / "noschema.json")) is None


def test_metrics_unknown_mode_warns_once_and_stays_off(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "bogus")
    monkeypatch.setattr(metrics, "_warned_mode", False)
    with pytest.warns(UserWarning, match="bogus"):
        assert metrics.metrics_mode() == "off"
    # second resolve: silent
    assert metrics.metrics_mode() == "off"


def test_package_counters_flow_when_on(monkeypatch, rng):
    """The wired seams actually land in the registry: a fused guarded
    solve bumps solver + guard-verdict counters; a plan-cache lookup
    bumps hit/miss."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    from pylops_mpi_tpu.ops.local import MatrixMult
    from pylops_mpi_tpu.tuning import cache
    mats = [rng.standard_normal((6, 4)) for _ in range(8)]
    Op = pmt.MPIBlockDiag([MatrixMult(m, dtype=np.float64)
                           for m in mats])
    xt = rng.standard_normal(8 * 4)
    y = pmt.DistributedArray.to_dist(
        np.concatenate([m @ xt[i * 4:(i + 1) * 4]
                        for i, m in enumerate(mats)]))
    pmt.cgls(Op, y, niter=5, tol=0.0)
    snap = metrics.snapshot()
    assert snap["counters"]["solver.cgls.solves"] == 1
    assert snap["counters"]["solver.cgls.iterations"] == 5
    assert snap["histograms"]["solver.cgls.wall_s"]["count"] == 1
    cache.clear_memory()
    assert cache.lookup("no-such-key") is None
    assert metrics.snapshot()["counters"]["tuning.cache.miss"] == 1


def test_heartbeat_embeds_metrics_snapshot(tmp_path, monkeypatch):
    path = str(tmp_path / "hb.json")
    # off: beats carry no metrics payload
    w = HeartbeatWriter(path, interval=30.0)
    w.beat()
    assert "metrics" not in read_heartbeat(path)
    # on: the live snapshot rides every beat
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.inc("solver.cg.solves", 4)
    w.beat()
    doc = read_heartbeat(path)
    assert doc["metrics"]["counters"]["solver.cg.solves"] == 4


# -------------------------------------------------- off-mode identity
def test_metrics_mode_hlo_bit_identical_and_no_callbacks(rng, monkeypatch):
    """The ISSUE 10 pin: metrics gate only host-side Python recorded
    AFTER the fused loops return — lowered HLO of fused CG and CGLS is
    bit-identical between off (default) and on, and metrics-on adds
    zero host callbacks."""
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((4, 4)) + 4 * np.eye(4)
            for _ in range(8)]
    spd = [m @ m.T for m in mats]
    Op = pmt.MPIBlockDiag([MatrixMult(m, dtype=np.float64)
                           for m in spd])
    xt = rng.standard_normal(8 * 4)
    y = pmt.DistributedArray.to_dist(
        np.concatenate([m @ xt[i * 4:(i + 1) * 4]
                        for i, m in enumerate(spd)]))
    x0 = pmt.DistributedArray.to_dist(np.zeros(8 * 4))

    def fcg(y_, x_, tol):
        return _cg_fused(Op, y_, x_, tol, niter=8)

    def fcgls(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=8)

    strip = (lambda s: re.sub(
        r'(HloModule\s+\S+|metadata=\{[^}]*\}|, module_name="[^"]*")',
        "", s))
    h_cg_off = hlo.compiled_hlo(fcg, y, x0, 0.0)
    h_cgls_off = hlo.compiled_hlo(fcgls, y, x0, 0.0, 0.0)
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    assert strip(hlo.compiled_hlo(fcg, y, x0, 0.0)) == strip(h_cg_off)
    assert strip(hlo.compiled_hlo(fcgls, y, x0, 0.0, 0.0)) == \
        strip(h_cgls_off)
    hlo.assert_no_host_callbacks(fcg, y, x0, 0.0)
    hlo.assert_no_host_callbacks(fcgls, y, x0, 0.0, 0.0)


# ---------------------------------------------------- trace aggregation
def _mk_rank_events(rank, clock_off_us, n=5, stall_from=None,
                    stall_us=5000.0, name="collective.ring_pass"):
    """Synthetic collective span stream: entry every 1000 us on the
    rank's own clock (shifted by ``clock_off_us``); from seq
    ``stall_from`` on, this rank enters ``stall_us`` late."""
    evs = []
    for i in range(n):
        ts = 1000.0 * i + clock_off_us
        if stall_from is not None and i >= stall_from:
            ts += stall_us
        evs.append({"name": name, "ph": "X", "ts": ts, "dur": 10.0,
                    "pid": 4000 + rank, "tid": 1, "cat": "collective",
                    "args": {"seq": i, "depth": 0}})
    return evs


def test_align_offsets_median_recovers_clock_skew():
    traces = {0: _mk_rank_events(0, 0.0),
              1: _mk_rank_events(1, -2500.0)}
    entries = {r: aggregate.collective_entries(t)
               for r, t in traces.items()}
    off = aggregate.align_offsets(entries)
    assert off[0] == 0.0 and abs(off[1] - 2500.0) < 1e-6


def test_merge_traces_stamps_skew_and_straggler():
    traces = {0: _mk_rank_events(0, 0.0, n=8),
              1: _mk_rank_events(1, -1000.0, n=8, stall_from=6)}
    m = aggregate.merge_traces(traces)
    assert m["ranks"] == [0, 1]
    assert abs(m["offsets_us"][1] - 1000.0) < 1e-6
    cols = {c["seq"]: c for c in m["collectives"]}
    assert len(cols) == 8
    for i in range(6):
        assert cols[i]["skew_us"] < 1e-6
    for i in (6, 7):
        assert cols[i]["skew_us"] == 5000.0
        assert cols[i]["straggler_rank"] == 1
    # merged events: pid=rank, aligned ts, args stamped on matches
    pids = {e["pid"] for e in m["events"] if e.get("ph") == "X"}
    assert pids == {0, 1}
    stamped = [e for e in m["events"] if e.get("ph") == "X"
               and e["args"].get("seq") == 7]
    assert all(e["args"]["skew_us"] == 5000.0
               and e["args"]["straggler_rank"] == 1 for e in stamped)


def test_merge_traces_tolerates_garbage_events():
    traces = {0: _mk_rank_events(0, 0.0) + ["junk", {"ph": "X"},
                                            {"name": "x", "ph": "X",
                                             "ts": "bad"}],
              1: _mk_rank_events(1, 0.0)}
    m = aggregate.merge_traces(traces)
    assert len(m["collectives"]) == 5


def test_load_events_tolerates_truncated_jsonl(tmp_path):
    p = tmp_path / "trace.rank0.jsonl"
    good = _mk_rank_events(0, 0.0, n=3)
    lines = [json.dumps(e) for e in good]
    lines.insert(1, '{"name": "trunca')   # killed mid-write
    lines.append("\x00\xff not json")
    p.write_text("\n".join(lines))
    evs = aggregate.load_events(str(p))
    assert len(evs) == 3
    assert aggregate.load_events(str(tmp_path / "missing.jsonl")) == []
    assert aggregate.guess_rank(str(p)) == 0


def test_span_tree_killed_worker_trace(tmp_path):
    """Regression (ISSUE 10 satellite): a SIGTERM post-mortem flush
    leaves ``ph="B"``-only open spans and possibly a truncated last
    line; ``span_tree`` must reconstruct a tree instead of raising."""
    evs = [
        {"name": "solver.cgls", "ph": "B", "ts": 0.0, "pid": 7,
         "tid": 1, "cat": "solver", "args": {"depth": 0, "open": True}},
        {"name": "op.matvec", "ph": "X", "ts": 5.0, "dur": 2.0,
         "pid": 7, "tid": 1, "cat": "operator", "args": {"depth": 1}},
        {"name": "collective.ring_pass", "ph": "B", "ts": 9.0, "pid": 7,
         "tid": 1, "cat": "collective",
         "args": {"depth": 1, "open": True, "seq": 0}},
    ]
    p = tmp_path / "killed.trace.jsonl"
    p.write_text("\n".join(json.dumps(e) for e in evs)
                 + '\n{"name": "cut-off mid wr')
    loaded = aggregate.load_events(str(p))
    roots = trace.span_tree(loaded)
    assert len(roots) == 1 and roots[0]["name"] == "solver.cgls"
    assert roots[0]["dur"] is None  # open span: unknown duration
    assert {c["name"] for c in roots[0]["children"]} == \
        {"op.matvec", "collective.ring_pass"}
    # garbage-only input: empty forest, no exception
    assert trace.span_tree(["x", {"ph": "M"}, None]) == []


def test_counter_events_multithreaded(monkeypatch):
    """The ph="C" counter path under concurrent emitters: every sample
    lands in the ring buffer intact (the satellite's missing
    multi-thread coverage)."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    n_threads, n_each = 8, 50

    def emit(k):
        for i in range(n_each):
            trace.counter(f"t{k}", {"i": float(i)})

    threads = [threading.Thread(target=emit, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = [e for e in trace.get_events() if e["ph"] == "C"]
    assert len(evs) == n_threads * n_each
    per = {}
    for e in evs:
        per.setdefault(e["name"], []).append(e["args"]["i"])
    assert all(sorted(v) == [float(i) for i in range(n_each)]
               for v in per.values())


def test_critical_path_walks_solver_chain():
    # buffer order = completion order (trace.py records ph="X" spans
    # when they EXIT): innermost-finished first, the solver root last
    evs = [
        {"name": "collective.ring_pass", "ph": "X", "ts": 20.0,
         "dur": 40.0, "pid": 0, "tid": 1, "cat": "collective",
         "args": {"depth": 2, "seq": 0}},
        {"name": "op.matvec", "ph": "X", "ts": 10.0, "dur": 60.0,
         "pid": 0, "tid": 1, "cat": "operator", "args": {"depth": 1}},
        {"name": "op.rmatvec", "ph": "X", "ts": 75.0, "dur": 20.0,
         "pid": 0, "tid": 1, "cat": "operator", "args": {"depth": 1}},
        {"name": "solver.cgls", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 0, "tid": 1, "cat": "solver", "args": {"depth": 0}},
    ]
    cps = aggregate.critical_path(evs)
    assert len(cps) == 1
    cp = cps[0]
    assert cp["solver"] == "solver.cgls" and cp["dur_us"] == 100.0
    names = [s["name"] for s in cp["path"]]
    assert names == ["op.matvec", "collective.ring_pass"]


# ------------------------------------------------------------------ CLI
def _write_trace(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def _run_cli(*args):
    p = subprocess.run(
        [sys.executable, "-m", "pylops_mpi_tpu.diagnostics", *args],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [ln for ln in p.stdout.strip().splitlines() if ln]
    return p.returncode, json.loads(lines[-1]) if lines else None, p.stderr


def test_cli_aggregate_merges_and_reports(tmp_path):
    _write_trace(tmp_path / "trace.rank0.jsonl",
                 _mk_rank_events(0, 0.0, n=6))
    _write_trace(tmp_path / "trace.rank1.jsonl",
                 _mk_rank_events(1, -800.0, n=6, stall_from=5))
    out = str(tmp_path / "merged.json")
    rc, summary, _ = _run_cli("aggregate", str(tmp_path), "--out", out)
    assert rc == 0
    assert summary["ok"] and summary["ranks"] == [0, 1]
    assert summary["n_collectives_matched"] == 6
    assert summary["max_skew"]["straggler_rank"] == 1
    assert summary["max_skew"]["skew_us"] == pytest.approx(5000.0)
    merged = json.load(open(out))
    pids = {e.get("pid") for e in merged["traceEvents"]
            if e.get("ph") == "X"}
    assert pids == {0, 1}


def test_cli_aggregate_no_inputs_fails(tmp_path):
    rc, summary, _ = _run_cli("aggregate", str(tmp_path / "empty"))
    assert rc == 1 and summary == {"ok": False, "error": "no trace files"}


def test_cli_metrics_summarizes_snapshots(tmp_path, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.inc("solver.cg.solves", 2)
    metrics.write_snapshot(str(tmp_path / "worker0.attempt0.metrics.json"))
    rc, summary, _ = _run_cli("metrics", str(tmp_path))
    assert rc == 0 and summary["ok"]
    assert summary["files"] == ["worker0.attempt0.metrics.json"]


# ------------------------------------------------------- job_report.json
def test_job_report_schema_roundtrip(tmp_path):
    """The supervisor persists a schema-versioned post-mortem with the
    failure classifications and harvested worker metrics; the file
    round-trips to the JobResult it came from."""
    code = ("import os, json, sys\n"
            "mf = os.environ['PYLOPS_MPI_TPU_METRICS_FILE']\n"
            "json.dump({'schema': 1, 'pid': os.getpid(), 'wall': 0.0,\n"
            "           'counters': {'solver.cg.solves': 2},\n"
            "           'gauges': {}, 'histograms': {}},\n"
            "          open(mf, 'w'))\n"
            "sys.exit(3 if os.environ['PYLOPS_MPI_TPU_PROCESS_ID']=='1'\n"
            "         and os.environ['PYLOPS_MPI_TPU_ATTEMPT']=='0'\n"
            "         else 0)\n")
    r = launch_job([sys.executable, "-c", code], 2,
                   heartbeat_interval=0.2, job_timeout_s=60,
                   logdir=str(tmp_path))
    assert r.ok and r.attempts == 2
    path = os.path.join(str(tmp_path), "job_report.json")
    assert os.path.exists(path)
    doc = json.load(open(path))
    assert doc["schema"] == supervisor.JOB_REPORT_SCHEMA
    assert doc["ok"] is True and doc["world_size"] == r.world_size
    assert doc["attempts"] == r.attempts
    assert doc["failures"] == [f.as_dict() for f in r.failures]
    assert doc["failures"][0]["kind"] == "exit"
    assert doc["returncodes"] == {str(k): v
                                  for k, v in r.returncodes.items()}
    # harvested worker metrics ride both the result and the report
    assert r.metrics[0]["counters"]["solver.cg.solves"] == 2
    assert doc["metrics"] == {str(k): v for k, v in r.metrics.items()}


def test_job_report_written_on_terminal_failure(tmp_path):
    r = launch_job([sys.executable, "-c", "import sys; sys.exit(2)"], 1,
                   heartbeat_interval=0.2, job_timeout_s=60,
                   max_relaunches=0, logdir=str(tmp_path))
    assert not r.ok
    doc = json.load(open(os.path.join(str(tmp_path), "job_report.json")))
    assert doc["ok"] is False
    assert [f["kind"] for f in doc["failures"]] == ["exit"]


# ------------------------------------------------- fleet-smoke acceptance
@pytest.mark.slow
def test_fleet_smoke_aggregation_names_straggler(tmp_path):
    """ISSUE 10 acceptance: a 2-process supervised job with METRICS=on
    + TRACE=spans produces per-rank traces whose aggregation yields a
    merged clock-aligned Chrome trace with both pids, every matched
    collective stamped with ``skew_us``/``straggler_rank``, and the
    injected ``faults.host_stall`` on rank 1 attributed to rank 1.
    The harvested metrics land in ``job_report.json``."""
    logdir = str(tmp_path)
    stall_s = 0.6
    env = {"PYLOPS_MPI_TPU_METRICS": "on",
           "PYLOPS_MPI_TPU_TRACE": "spans",
           "PYLOPS_FLEET_LOGDIR": logdir,
           "PYLOPS_FLEET_STALL_RANK": "1",
           "PYLOPS_FLEET_STALL_S": str(stall_s),
           # workers pin their own 4 virtual devices
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    budget = stage_budget("multihost_chaos")
    r = launch_job([os.path.join(ROOT, "tests", "fleet_obs_worker.py")],
                   2, heartbeat_interval=0.4, job_timeout_s=budget,
                   env=env, logdir=logdir)
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})

    # per-worker metrics harvested into the result and the report
    report = json.load(open(os.path.join(logdir, "job_report.json")))
    for rank in (0, 1):
        counters = report["metrics"][str(rank)]["counters"]
        assert counters["solver.cgls.solves"] == 1
        assert counters["collective.all_to_all_resharding.calls"] == 8
        assert counters["collective.all_to_all_resharding.bytes"] > 0

    # aggregate the two rank traces through the CLI
    out = os.path.join(logdir, "merged_trace.json")
    rc, summary, stderr = _run_cli("aggregate", logdir, "--out", out)
    assert rc == 0, stderr
    assert summary["ranks"] == [0, 1]
    assert summary["n_collectives_matched"] >= 8
    merged = json.load(open(out))
    pids = {e.get("pid") for e in merged["traceEvents"]
            if e.get("ph") == "X"}
    assert pids == {0, 1}
    # every matched collective carries the stamps
    stamped = [e for e in merged["traceEvents"]
               if e.get("cat") == "collective" and e.get("ph") == "X"
               and "seq" in e.get("args", {})]
    assert stamped and all("skew_us" in e["args"]
                           and "straggler_rank" in e["args"]
                           for e in stamped)
    # the injected stall is attributed to rank 1 with >= half its
    # magnitude surviving the median alignment (6 warm vs 2 post)
    mx = summary["max_skew"]
    assert mx["straggler_rank"] == 1
    assert mx["skew_us"] >= 0.5 * stall_s * 1e6
    # critical path names the solver on both ranks
    solvers = {cp["solver"] for cp in summary["critical_path"]}
    assert "solver.cgls" in solvers
