"""Elastic job runtime suite (ISSUE 8): heartbeats, the collective
watchdog, the supervisor's launch/classify/shrink/relaunch loop, and
the end-to-end chaos acceptance (2-process segmented CGLS, one worker
SIGSTOPped mid-solve, job relaunched single-process on a shrunk mesh
with the checkpoint elastically resharded).

The quick supervisor tests drive jax-free ``python -c`` workers so the
classify/relaunch machinery is exercised in milliseconds; the real
multi-process solve lives in the ``slow``-marked chaos test
(``tests/elastic_worker.py``)."""

import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.diagnostics.profiler import STAGE_BUDGETS, stage_budget
from pylops_mpi_tpu.resilience import elastic, supervisor
from pylops_mpi_tpu.resilience.elastic import (
    HeartbeatWriter, WatchdogTimeout, heartbeat_interval, read_heartbeat,
    watched_call, watchdog_enabled, watchdog_mode, watchdog_timeout,
    worker_config)
from pylops_mpi_tpu.resilience.supervisor import launch_job
from pylops_mpi_tpu.solvers.basic import _cgls_fused
from pylops_mpi_tpu.utils import hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ELASTIC_ENV = ("PYLOPS_MPI_TPU_COORDINATOR",
                "PYLOPS_MPI_TPU_NUM_PROCESSES",
                "PYLOPS_MPI_TPU_PROCESS_ID", "PYLOPS_MPI_TPU_ATTEMPT",
                "PYLOPS_MPI_TPU_HEARTBEAT_FILE", "PYLOPS_MPI_TPU_HEARTBEAT",
                "PYLOPS_MPI_TPU_WATCHDOG",
                "PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT",
                "PYLOPS_MPI_TPU_INPLACE", "PYLOPS_MPI_TPU_QUORUM",
                "PYLOPS_MPI_TPU_RECONFIG_FILE",
                "PYLOPS_MPI_TPU_FAULT_KILL_RESHARD")


@pytest.fixture(autouse=True)
def _unsupervised(monkeypatch):
    """Tests must not inherit a supervisor env contract (e.g. when the
    test process itself runs under a supervised CI wrapper)."""
    for name in _ELASTIC_ENV:
        monkeypatch.delenv(name, raising=False)
    elastic.stop_heartbeat()
    yield
    elastic.stop_heartbeat()


# --------------------------------------------------------- heartbeats
def test_heartbeat_writer_beats_and_parses(tmp_path):
    hb = str(tmp_path / "w.hb")
    w = HeartbeatWriter(hb, interval=0.05)
    w.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            beat = read_heartbeat(hb)
            if beat is not None and beat["seq"] >= 3:
                break
            time.sleep(0.02)
        beat = read_heartbeat(hb)
        assert beat is not None and beat["seq"] >= 3
        assert beat["pid"] == os.getpid()
    finally:
        w.stop()
    assert not w.is_alive()
    # no torn writes: the beat file is always complete JSON
    with open(hb) as f:
        json.loads(f.read())


def test_maybe_start_heartbeat_is_noop_unsupervised():
    assert elastic.maybe_start_heartbeat() is None


def test_start_heartbeat_env_contract(tmp_path, monkeypatch):
    hb = str(tmp_path / "env.hb")
    monkeypatch.setenv("PYLOPS_MPI_TPU_HEARTBEAT_FILE", hb)
    monkeypatch.setenv("PYLOPS_MPI_TPU_HEARTBEAT", "0.05")
    assert heartbeat_interval() == 0.05
    w = elastic.maybe_start_heartbeat()
    assert w is not None and w.path == hb
    assert elastic.maybe_start_heartbeat() is w  # idempotent
    deadline = time.monotonic() + 5.0
    while not os.path.exists(hb) and time.monotonic() < deadline:
        time.sleep(0.02)
    assert read_heartbeat(hb) is not None
    elastic.stop_heartbeat()


def test_worker_config_reads_contract(monkeypatch):
    assert worker_config().coordinator is None
    monkeypatch.setenv("PYLOPS_MPI_TPU_COORDINATOR", "127.0.0.1:777")
    monkeypatch.setenv("PYLOPS_MPI_TPU_NUM_PROCESSES", "3")
    monkeypatch.setenv("PYLOPS_MPI_TPU_PROCESS_ID", "2")
    monkeypatch.setenv("PYLOPS_MPI_TPU_ATTEMPT", "1")
    cfg = worker_config()
    assert cfg.coordinator == "127.0.0.1:777"
    assert (cfg.num_processes, cfg.process_id, cfg.attempt) == (3, 2, 1)


# ----------------------------------------------------------- watchdog
def test_watchdog_auto_off_when_unsupervised():
    assert watchdog_mode() == "auto"
    assert not watchdog_enabled()
    # disarmed: a direct call, no trace events, result passes through
    trace.clear_events()
    assert watched_call(lambda a: a * 2, 21, stage="checkpoint_io") == 42
    assert trace.get_events() == []


def test_watchdog_auto_arms_under_supervision(tmp_path, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_HEARTBEAT_FILE",
                       str(tmp_path / "x.hb"))
    assert watchdog_enabled()
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "off")
    assert not watchdog_enabled()  # explicit off beats supervision


def test_watchdog_on_timeout_raises_classified(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    with pytest.raises(WatchdogTimeout, match="multihost_init"):
        watched_call(time.sleep, 10.0, stage="multihost_init",
                     timeout_s=0.2)
    names = [e["name"] for e in trace.get_events()]
    assert "resilience.watchdog_timeout" in names
    trace.clear_events()


def test_watchdog_relays_result_and_exception(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "on")
    assert watched_call(lambda: "done", stage="checkpoint_io") == "done"
    with pytest.raises(ZeroDivisionError):
        watched_call(lambda: 1 / 0, stage="checkpoint_io")


def test_watchdog_nested_runs_direct(monkeypatch):
    """A watched phase that itself calls a watched phase (checkpoint
    save inside a harvest stage) must not stack threads/deadlines."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "on")
    import threading
    outer_thread = {}

    def inner():
        return threading.get_ident()

    def outer():
        outer_thread["outer"] = threading.get_ident()
        return watched_call(inner, stage="checkpoint_io")

    inner_tid = watched_call(outer, stage="multihost_init")
    assert inner_tid == outer_thread["outer"]  # same thread: direct call


def test_watchdog_timeout_resolution(monkeypatch):
    # stage budget row ("tpu" column) is the default deadline
    assert watchdog_timeout("multihost_init") == \
        STAGE_BUDGETS["multihost_init"]
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT", "7.5")
    assert watchdog_timeout("multihost_init") == 7.5
    assert watchdog_timeout("checkpoint_io") == 7.5  # global override


def test_new_stages_in_budget_table():
    for stage in ("multihost_init", "checkpoint_io", "multihost_chaos"):
        assert stage in STAGE_BUDGETS
        assert stage_budget(stage) == STAGE_BUDGETS[stage]


def test_unknown_watchdog_mode_warns_once(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "sideways")
    monkeypatch.setattr(elastic, "_warned_wd", False)
    with pytest.warns(UserWarning, match="PYLOPS_MPI_TPU_WATCHDOG"):
        assert watchdog_mode() == "auto"


# --------------------------------------------- supervisor quick tests
def _job(argv, n, **kw):
    kw.setdefault("heartbeat_interval", 0.2)
    kw.setdefault("job_timeout_s", 60)
    return launch_job(argv, n, **kw)


def test_launch_job_success_and_env_contract():
    code = ("import os; print(os.environ['PYLOPS_MPI_TPU_PROCESS_ID'],"
            "os.environ['PYLOPS_MPI_TPU_NUM_PROCESSES'],"
            "os.environ['PYLOPS_MPI_TPU_ATTEMPT'],"
            "os.environ['PYLOPS_MPI_TPU_COORDINATOR'])")
    r = _job([sys.executable, "-c", code], 2)
    assert r.ok and r.attempts == 1 and r.world_size == 2
    assert r.failures == []
    for rank in (0, 1):
        pid_, world_, attempt_, coord = r.outputs[rank].split()
        assert (int(pid_), int(world_), int(attempt_)) == (rank, 2, 0)
        assert re.match(r"127\.0\.0\.1:\d+", coord)


def test_launch_job_placeholders():
    r = _job([sys.executable, "-c",
              "import sys; print('{rank}/{world}@{attempt}:{port}')"], 2)
    assert r.ok
    assert r.outputs[1].startswith("1/2@0:")


def test_launch_job_exit_classified_and_shrunk():
    code = ("import os, sys;"
            "sys.exit(3 if os.environ['PYLOPS_MPI_TPU_PROCESS_ID']=='1'"
            " and os.environ['PYLOPS_MPI_TPU_ATTEMPT']=='0' else 0)")
    r = _job([sys.executable, "-c", code], 2)
    assert r.ok and r.attempts == 2 and r.world_size == 1
    f = r.failures[0]
    assert (f.kind, f.returncode, f.slot) == ("exit", 3, 1)


def test_launch_job_signal_classified():
    code = ("import os, signal;"
            "(os.environ['PYLOPS_MPI_TPU_ATTEMPT'],"
            " os.environ['PYLOPS_MPI_TPU_PROCESS_ID']) == ('0', '1') "
            "and os.kill(os.getpid(), signal.SIGKILL)")
    r = _job([sys.executable, "-c", code], 2, max_relaunches=1)
    assert r.ok and r.attempts == 2 and r.world_size == 1
    f = r.failures[0]
    assert f.kind == "signal" and f.returncode == -9
    assert "SIGKILL" in f.detail


def test_launch_job_stale_heartbeat_sigstop():
    """The acceptance-criteria detection bound, on a jax-free worker:
    a SIGSTOPped (alive but frozen) worker is classified
    ``stale_heartbeat`` within 2x the heartbeat interval (+ a poll/IO
    margin), and the job relaunches without its slot."""
    hb_interval = 0.2
    code = ("import os, time\n"
            "hb = os.environ['PYLOPS_MPI_TPU_HEARTBEAT_FILE']\n"
            "iv = float(os.environ['PYLOPS_MPI_TPU_HEARTBEAT'])\n"
            "if os.environ['PYLOPS_MPI_TPU_ATTEMPT'] == '0':\n"
            "    while True:\n"
            "        with open(hb, 'w') as f:\n"
            "            f.write('beat')\n"
            "        time.sleep(iv)\n")
    stopped = []

    def on_poll(attempt, workers):
        if attempt == 0 and not stopped:
            w = workers[0]
            if os.path.exists(w.heartbeat_path) and w.alive():
                w.proc.send_signal(signal.SIGSTOP)
                stopped.append(time.monotonic())

    r = _job([sys.executable, "-c", code], 2, on_poll=on_poll,
             heartbeat_interval=hb_interval, stale_factor=2.0)
    assert r.ok and r.attempts == 2 and r.world_size == 1
    f = r.failures[0]
    assert f.kind == "stale_heartbeat" and f.slot == 0
    detected_at = stopped[0] and time.monotonic()  # noqa: F841
    # detection latency after the freeze: the beat written just before
    # the SIGSTOP goes stale after 2x interval; allow 1 interval of
    # in-flight beat + poll/filesystem margin
    assert f.detected_after_s < 60.0
    m = re.search(r"no heartbeat for ([\d.]+)s", f.detail)
    assert m and float(m.group(1)) <= 2 * hb_interval + 1.0


def test_launch_job_no_shrink_keeps_world():
    code = ("import os, sys;"
            "sys.exit(1 if os.environ['PYLOPS_MPI_TPU_ATTEMPT']=='0' "
            "and os.environ['PYLOPS_MPI_TPU_PROCESS_ID']=='0' else 0)")
    r = _job([sys.executable, "-c", code], 2, shrink=False)
    assert r.ok and r.attempts == 2 and r.world_size == 2


def test_launch_job_timeout_is_terminal(tmp_path):
    r = _job([sys.executable, "-c", "import time; time.sleep(60)"], 1,
             job_timeout_s=1.0, grace_s=30.0)
    assert not r.ok and r.attempts == 1
    assert r.failures[-1].kind == "timeout"


def test_launch_job_budget_exhausted_reports_failures():
    r = _job([sys.executable, "-c", "import sys; sys.exit(2)"], 2,
             max_relaunches=1)
    assert not r.ok
    assert len(r.failures) == 2  # one per attempt
    assert all(f.kind == "exit" for f in r.failures)


def test_launch_job_logs_kept(tmp_path):
    r = _job([sys.executable, "-c", "print('hello from worker')"], 1,
             logdir=str(tmp_path))
    assert r.ok and "hello from worker" in r.outputs[0]
    assert r.logdir == str(tmp_path)
    assert any(p.endswith(".log") for p in os.listdir(tmp_path))


# ------------------------------------- in-place recovery (ISSUE 13)
def test_inplace_mode_and_arming(monkeypatch):
    assert elastic.inplace_mode() == "auto"
    assert not elastic.inplace_armed()  # auto + no assignment
    monkeypatch.setenv("PYLOPS_MPI_TPU_RECONFIG_FILE", "/tmp/rc.json")
    assert elastic.inplace_armed()      # auto + supervisor assignment
    monkeypatch.setenv("PYLOPS_MPI_TPU_INPLACE", "off")
    assert not elastic.inplace_armed()  # explicit off beats assignment
    monkeypatch.delenv("PYLOPS_MPI_TPU_RECONFIG_FILE")
    monkeypatch.setenv("PYLOPS_MPI_TPU_INPLACE", "on")
    assert elastic.inplace_armed()      # explicit on needs no file


def test_unknown_inplace_mode_warns_once(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_INPLACE", "sideways")
    monkeypatch.setattr(elastic, "_warned_ip", False)
    with pytest.warns(UserWarning, match="PYLOPS_MPI_TPU_INPLACE"):
        assert elastic.inplace_mode() == "auto"


def test_quorum_fraction_parsing(monkeypatch):
    assert elastic.quorum_fraction() == 0.5
    monkeypatch.setenv("PYLOPS_MPI_TPU_QUORUM", "0.75")
    assert elastic.quorum_fraction() == 0.75
    monkeypatch.setenv("PYLOPS_MPI_TPU_QUORUM", "7")
    assert elastic.quorum_fraction() == 1.0   # clamped into (0, 1]
    monkeypatch.setenv("PYLOPS_MPI_TPU_QUORUM", "junk")
    assert elastic.quorum_fraction() == 0.5   # malformed -> default


def test_pending_reconfig_lifecycle(tmp_path, monkeypatch):
    rcf = str(tmp_path / "rc.json")
    monkeypatch.setenv("PYLOPS_MPI_TPU_RECONFIG_FILE", rcf)
    assert elastic.pending_reconfig() is None      # no file yet
    with open(rcf, "w") as f:
        f.write("{not json")                       # torn write: skip
    assert elastic.pending_reconfig() is None
    with open(rcf, "w") as f:
        json.dump({"attempt": 0}, f)               # not newer than ours
    assert elastic.pending_reconfig() is None
    doc = {"attempt": 1, "num_processes": 1, "process_id": 0,
           "coordinator": None, "lost_slot": 1}
    with open(rcf, "w") as f:
        json.dump(doc, f)
    rc = elastic.pending_reconfig()
    assert rc == doc
    cfg = elastic.apply_reconfig(rc)
    assert (cfg.num_processes, cfg.process_id, cfg.attempt) == (1, 0, 1)
    # applying bumped PYLOPS_MPI_TPU_ATTEMPT, which consumes the doc
    assert elastic.pending_reconfig() is None


def test_reform_mesh_refuses_multiprocess(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_NUM_PROCESSES", "2")
    with pytest.raises(RuntimeError, match="checkpoint"):
        elastic.reform_mesh(worker_config())


def test_reform_mesh_single_process_local_devices(monkeypatch):
    import jax
    monkeypatch.setenv("PYLOPS_MPI_TPU_NUM_PROCESSES", "1")
    mesh = elastic.reform_mesh(worker_config())
    assert mesh.devices.size == len(jax.local_devices())


def test_launch_job_inplace_single_survivor_reconfig(tmp_path):
    """ISSUE 13: a 2-worker job loses one worker; with ``inplace=True``
    the supervisor keeps the survivor ALIVE and hands it a reconfig
    file naming the shrunk world instead of killing + relaunching."""
    code = (
        "import os, sys, time, json\n"
        "rcf = os.environ['PYLOPS_MPI_TPU_RECONFIG_FILE']\n"
        "if os.environ['PYLOPS_MPI_TPU_PROCESS_ID'] == '1':\n"
        "    sys.exit(3)\n"
        "for _ in range(1200):\n"
        "    if os.path.exists(rcf):\n"
        "        print('RECONFIG', json.dumps(json.load(open(rcf))))\n"
        "        sys.exit(0)\n"
        "    time.sleep(0.05)\n"
        "sys.exit(9)\n")
    r = _job([sys.executable, "-c", code], 2, inplace=True,
             logdir=str(tmp_path))
    assert r.ok, r.failures
    assert r.attempts == 2 and r.world_size == 1
    assert [f.kind for f in r.failures] == ["exit"]
    doc = json.loads(r.outputs[0].split("RECONFIG ", 1)[1])
    assert doc == {"attempt": 1, "num_processes": 1, "process_id": 0,
                   "coordinator": None, "lost_slot": 1}


def test_launch_job_inplace_multi_survivor_falls_back(tmp_path):
    """Two live survivors cannot re-form a mesh in place (the
    ``jax.distributed`` teardown barrier hangs against a dead peer),
    so the supervisor takes the classic kill-all + shrink ladder and
    never writes a reconfig."""
    code = (
        "import os, sys, time\n"
        "if os.environ['PYLOPS_MPI_TPU_ATTEMPT'] == '0':\n"
        "    if os.environ['PYLOPS_MPI_TPU_PROCESS_ID'] == '2':\n"
        "        sys.exit(3)\n"
        "    time.sleep(120)\n"
        "sys.exit(0)\n")
    r = _job([sys.executable, "-c", code], 3, inplace=True,
             logdir=str(tmp_path))
    assert r.ok and r.attempts == 2 and r.world_size == 2
    assert not any(p.endswith(".reconfig.json")
                   for p in os.listdir(tmp_path))


def test_launch_job_inplace_below_quorum_falls_back(tmp_path):
    """quorum=0.9 of a 2-world needs 2 survivors; 1 survivor is below
    quorum, so in-place refuses and the relaunch ladder runs."""
    code = (
        "import os, sys, time\n"
        "if os.environ['PYLOPS_MPI_TPU_ATTEMPT'] == '0':\n"
        "    if os.environ['PYLOPS_MPI_TPU_PROCESS_ID'] == '1':\n"
        "        sys.exit(3)\n"
        "    time.sleep(120)\n"
        "sys.exit(0)\n")
    r = _job([sys.executable, "-c", code], 2, inplace=True, quorum=0.9,
             logdir=str(tmp_path))
    assert r.ok and r.attempts == 2 and r.world_size == 1
    assert not any(p.endswith(".reconfig.json")
                   for p in os.listdir(tmp_path))


# -------------------------------------------------- off-mode identity
def test_watchdog_off_mode_hlo_and_trace_identical(rng, monkeypatch):
    """Arming gates only host-side behavior: lowered HLO of a fused
    solve is bit-identical between the default (unsupervised) mode and
    explicit WATCHDOG=off, and the disarmed watchdog emits zero trace
    events around a watched phase."""
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((6, 4)) for _ in range(8)]
    Op = pmt.MPIBlockDiag([MatrixMult(m, dtype=np.float64)
                           for m in mats])
    xt = rng.standard_normal(8 * 4)
    y = pmt.DistributedArray.to_dist(
        np.concatenate([m @ xt[i * 4:(i + 1) * 4]
                        for i, m in enumerate(mats)]))
    x0 = pmt.DistributedArray.to_dist(np.zeros(8 * 4))

    def f(y_, x_, damp, tol):
        return _cgls_fused(Op, y_, x_, damp, tol, niter=10)

    strip = (lambda s: re.sub(
        r'(HloModule\s+\S+|metadata=\{[^}]*\}|, module_name="[^"]*")',
        "", s))
    h_default = hlo.compiled_hlo(f, y, x0, 0.0, 0.0)
    monkeypatch.setenv("PYLOPS_MPI_TPU_WATCHDOG", "off")
    h_off = hlo.compiled_hlo(f, y, x0, 0.0, 0.0)
    assert strip(h_default) == strip(h_off)

    monkeypatch.delenv("PYLOPS_MPI_TPU_WATCHDOG")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    watched_call(lambda: None, stage="checkpoint_io")
    assert trace.get_events() == []  # disarmed: not even a span
    trace.clear_events()


# ------------------------------------------------- chaos acceptance
@pytest.mark.slow
def test_chaos_kill_recover_resume(tmp_path):
    """ISSUE 8 acceptance: 2-process segmented CGLS; the supervisor
    SIGSTOPs worker 0 mid-solve (after the first epoch checkpoint
    lands), classifies the stale heartbeat within 2x the beat interval,
    relaunches single-process on the shrunk mesh, the orbax carry is
    elastically resharded 8 -> 4 devices, and the resumed final iterate
    matches the uninterrupted trajectory within 1e-6."""
    hb = 0.4
    ckpt = str(tmp_path / "carry.orbax")
    out = str(tmp_path / "final_x.npy")
    env = {"PYLOPS_ELASTIC_CKPT": ckpt, "PYLOPS_ELASTIC_OUT": out,
           # workers pin their own 4 virtual devices; scrub inherited
           # forcing (same scrub as test_multihost)
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    stopped = []

    def on_poll(attempt, workers):
        if attempt == 0 and not stopped:
            w = workers[0]
            if os.path.isdir(ckpt) and w.alive():
                w.proc.send_signal(signal.SIGSTOP)
                stopped.append(time.monotonic())

    budget = stage_budget("multihost_chaos")
    r = launch_job([os.path.join(ROOT, "tests", "elastic_worker.py")],
                   2, heartbeat_interval=hb, stale_factor=2.0,
                   on_poll=on_poll, job_timeout_s=budget, env=env)
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})
    assert r.attempts == 2 and r.world_size == 1
    f = r.failures[0]
    assert f.kind == "stale_heartbeat" and f.slot == 0
    # detection bound: the last pre-freeze beat goes stale after
    # 2 x interval; one interval of in-flight beat + poll margin
    m = re.search(r"no heartbeat for ([\d.]+)s", f.detail)
    assert m and float(m.group(1)) <= 2 * hb + 1.0, f.detail

    # the resumed (shrunk, 4-device) final iterate vs the
    # uninterrupted reference computed in-process on 8 devices
    ref = _uninterrupted_reference()
    got = np.load(out)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel < 1e-6, rel


def _trace_names(path):
    names = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                names.append(json.loads(line).get("name", ""))
    return names


@pytest.mark.slow
def test_chaos_inplace_kill_recover(tmp_path):
    """ISSUE 13 acceptance: 2-process segmented CGLS with
    ``launch_job(inplace=True)``; one worker is SIGKILLed mid-solve
    (inside the epoch-boundary sleep, after the carry was banked). The
    supervisor classifies the death, keeps the survivor alive and
    writes it a reconfig; the survivor re-forms its local mesh,
    replants the banked carry through the bounded-memory resharding
    planner and resumes — with ZERO checkpoint reads on the recovery
    path (trace-pinned) and a final iterate matching the uninterrupted
    reference."""
    ckpt = str(tmp_path / "carry.orbax")
    out = str(tmp_path / "final_x.npy")
    mark = str(tmp_path / "epoch.mark")
    tracef = str(tmp_path / "survivor.trace.jsonl")
    env = {"PYLOPS_ELASTIC_CKPT": ckpt, "PYLOPS_ELASTIC_OUT": out,
           "PYLOPS_ELASTIC_EPOCH_MARK": mark,
           "PYLOPS_ELASTIC_EPOCH_SLEEP": "2.0",
           "PYLOPS_MPI_TPU_TRACE": "spans",
           "PYLOPS_MPI_TPU_TRACE_FILE": tracef,
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    killed = []

    def on_poll(attempt, workers):
        # kill worker slot 1 INSIDE the sleep that follows an epoch's
        # bank+save: outside any gloo collective (a peer dying inside
        # one wedges the survivor), after state worth recovering exists
        if not killed and os.path.exists(mark):
            for w in workers:
                if w.slot == 1 and w.alive():
                    w.proc.send_signal(signal.SIGKILL)
                    killed.append(w.slot)

    budget = stage_budget("multihost_chaos")
    r = launch_job([os.path.join(ROOT, "tests", "elastic_worker.py")],
                   2, heartbeat_interval=0.4, stale_factor=2.0,
                   on_poll=on_poll, job_timeout_s=budget, env=env,
                   inplace=True)
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})
    assert r.attempts == 2 and r.world_size == 1
    assert [f.kind for f in r.failures] == ["signal"]
    assert r.failures[0].slot == 1
    assert "ELASTIC OK" in r.outputs[0]
    assert "INPLACE FALLBACK" not in r.outputs[0]

    # the trace pin: the survivor recovered through the in-place
    # collective path and never touched the checkpoint reader
    names = _trace_names(tracef)
    assert "resilience.carry_banked" in names
    assert "resilience.mesh_reformed" in names
    assert "resilience.inplace_recovery" in names
    assert "collective.reshard.step" in names
    assert "checkpoint.load" not in names

    ref = _uninterrupted_reference()
    got = np.load(out)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel < 1e-6, rel


@pytest.mark.slow
def test_chaos_kill_mid_reshard_falls_back(tmp_path):
    """ISSUE 13 satellite: the survivor is itself killed MID-RESHARD
    (the ``faults.maybe_kill_reshard`` seam fires on the first planner
    step of the in-place restore), and the job still completes through
    the checkpoint-relaunch fallback with zero divergence. The
    relaunched worker's trace HAS the checkpoint read the in-place
    path avoids."""
    ckpt = str(tmp_path / "carry.orbax")
    out = str(tmp_path / "final_x.npy")
    mark = str(tmp_path / "epoch.mark")
    tracef = str(tmp_path / "worker.trace.jsonl")
    env = {"PYLOPS_ELASTIC_CKPT": ckpt, "PYLOPS_ELASTIC_OUT": out,
           "PYLOPS_ELASTIC_EPOCH_MARK": mark,
           "PYLOPS_ELASTIC_EPOCH_SLEEP": "2.0",
           "PYLOPS_MPI_TPU_TRACE": "spans",
           "PYLOPS_MPI_TPU_TRACE_FILE": tracef,
           # SIGKILL on the FIRST reshard step: mid in-place restore.
           # The checkpoint restore path never touches the planner, so
           # the relaunched worker survives the same env.
           "PYLOPS_MPI_TPU_FAULT_KILL_RESHARD": "1",
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    killed = []

    def on_poll(attempt, workers):
        if not killed and os.path.exists(mark):
            for w in workers:
                if w.slot == 1 and w.alive():
                    w.proc.send_signal(signal.SIGKILL)
                    killed.append(w.slot)

    budget = stage_budget("multihost_chaos")
    r = launch_job([os.path.join(ROOT, "tests", "elastic_worker.py")],
                   2, heartbeat_interval=0.4, stale_factor=2.0,
                   on_poll=on_poll, job_timeout_s=budget, env=env,
                   inplace=True, shrink=False, max_relaunches=2)
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})
    # launch + in-place reconfig + checkpoint relaunch
    assert r.attempts == 3 and r.world_size == 1
    assert [f.kind for f in r.failures] == ["signal", "signal"]
    assert [f.slot for f in r.failures] == [1, 0]
    assert "ELASTIC OK" in r.outputs[0]

    # the relaunched worker resumed from the checkpoint: its trace has
    # the read, and no in-place recovery
    names = _trace_names(tracef)
    assert "checkpoint.load" in names
    assert "resilience.inplace_recovery" not in names

    ref = _uninterrupted_reference()
    got = np.load(out)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel < 1e-6, rel


@pytest.mark.slow
def test_chaos_kill_mid_spill_falls_back(tmp_path):
    """ISSUE 14 satellite: the survivor is killed MID-SPILL — with
    ``PYLOPS_MPI_TPU_SPILL=on`` the in-place restore's placement is
    host-staged, and the ``faults.maybe_kill_spill`` seam SIGKILLs on
    its first ``host_stage`` step. The job still completes through the
    checkpoint-relaunch fallback with zero divergence: the checkpoint
    restore path never touches the concrete planner (no budget env is
    set), so the relaunched worker survives the same env."""
    ckpt = str(tmp_path / "carry.orbax")
    out = str(tmp_path / "final_x.npy")
    mark = str(tmp_path / "epoch.mark")
    tracef = str(tmp_path / "worker.trace.jsonl")
    env = {"PYLOPS_ELASTIC_CKPT": ckpt, "PYLOPS_ELASTIC_OUT": out,
           "PYLOPS_ELASTIC_EPOCH_MARK": mark,
           "PYLOPS_ELASTIC_EPOCH_SLEEP": "2.0",
           "PYLOPS_MPI_TPU_TRACE": "spans",
           "PYLOPS_MPI_TPU_TRACE_FILE": tracef,
           "PYLOPS_MPI_TPU_SPILL": "on",
           "PYLOPS_MPI_TPU_FAULT_KILL_SPILL": "1",
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    killed = []

    def on_poll(attempt, workers):
        if not killed and os.path.exists(mark):
            for w in workers:
                if w.slot == 1 and w.alive():
                    w.proc.send_signal(signal.SIGKILL)
                    killed.append(w.slot)

    budget = stage_budget("multihost_chaos")
    r = launch_job([os.path.join(ROOT, "tests", "elastic_worker.py")],
                   2, heartbeat_interval=0.4, stale_factor=2.0,
                   on_poll=on_poll, job_timeout_s=budget, env=env,
                   inplace=True, shrink=False, max_relaunches=2)
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})
    # launch + in-place reconfig (killed mid-spill) + checkpoint relaunch
    assert r.attempts == 3 and r.world_size == 1
    assert [f.kind for f in r.failures] == ["signal", "signal"]
    assert [f.slot for f in r.failures] == [1, 0]
    assert "ELASTIC OK" in r.outputs[0]

    # the relaunched worker resumed from the checkpoint: its trace has
    # the read, and no in-place recovery
    names = _trace_names(tracef)
    assert "checkpoint.load" in names
    assert "resilience.inplace_recovery" not in names

    ref = _uninterrupted_reference()
    got = np.load(out)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel < 1e-6, rel


def _uninterrupted_reference():
    """The chaos worker's exact problem (seed 0, f64), solved
    uninterrupted with the same segmented schedule."""
    from pylops_mpi_tpu.ops.local import MatrixMult
    rng = np.random.default_rng(0)
    n, nb = 24, 8
    blocks = []
    for _ in range(nb):
        b = rng.standard_normal((n, n)) / np.sqrt(n)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        blocks.append(b)
    xt = rng.standard_normal(nb * n)
    y = np.concatenate([b @ xt[i * n:(i + 1) * n]
                        for i, b in enumerate(blocks)])
    mesh = pmt.make_mesh()
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float64)
                           for b in blocks], mesh=mesh)
    dy = pmt.DistributedArray.to_dist(y, mesh=mesh)
    x0 = pmt.DistributedArray.to_dist(np.zeros_like(xt), mesh=mesh)
    res = pmt.cgls_segmented(Op, dy, x0=x0, niter=60, tol=0.0, epoch=5)
    return np.asarray(res.x.asarray())
