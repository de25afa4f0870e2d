"""Autotuning subsystem (round 10): plan seam, cache robustness,
search budget/replay, knob registry, roofline re-bucketing.

The two hard pins:

- ``PYLOPS_MPI_TPU_TUNE=off`` (and unset) is a NO-OP: operators lower
  to bit-identical programs with the tuner package never consulted —
  the same exact-equality pattern as the overlap pin
  (``test_overlap.py::test_summa_off_bit_identical``).
- A cache written once is replayed with ZERO timing trials (counted
  via the structured ``tuning.trial`` trace events).
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.distributedarray import DistributedArray
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.tuning import cache as tcache
from pylops_mpi_tpu.tuning import plan as tplan
from pylops_mpi_tpu.tuning import search as tsearch
from pylops_mpi_tpu.tuning import space as tspace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tuning_isolation(monkeypatch):
    """Every test starts with the tuner off, no cache file, an empty
    in-memory store and a clean trace buffer."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE_CACHE", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_TRACE", raising=False)
    tcache.clear_memory()
    tplan.reset_applied()
    trace.clear_events()
    yield
    tcache.clear_memory()
    tplan.reset_applied()
    trace.clear_events()


def _events(name):
    return [e for e in trace.get_events() if e.get("name") == name]


# ------------------------------------------------------------ mode seam
def test_tune_mode_resolution(monkeypatch):
    assert tplan.tune_mode() == "off"
    for raw, want in (("on", "on"), ("ON ", "on"), ("auto", "auto"),
                      ("1", "on"), ("", "off"), ("0", "off")):
        monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", raw)
        assert tplan.tune_mode() == want
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "bogus")
    tplan._warned_mode = False
    with pytest.warns(UserWarning, match="PYLOPS_MPI_TPU_TUNE"):
        assert tplan.tune_mode() == "off"
    tplan._warned_mode = False


def test_get_plan_off_returns_none():
    assert tplan.get_plan("matrixmult", shape=(8, 8, 4),
                          n_dev=8) is None
    assert tplan.applied_provenance("matrixmult") == "default"


def test_unknown_op_returns_none(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    assert tplan.get_plan("no_such_family", shape=(8,), n_dev=1) is None


# ----------------------------------------- off == bit-identical programs
def _lowered(op, dx):
    return jax.jit(op._matvec).lower(dx).as_text()


def test_tune_off_bit_identical_summa(rng, monkeypatch):
    """TUNE=off and TUNE-unset lower the SUMMA matvec to the exact
    same program, and exact array equality holds (the overlap-pin
    pattern); both schedules."""
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    dx = DistributedArray.to_dist(X.ravel())
    for schedule in ("gather", "stat_a"):
        unset = pmt.MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                                  schedule=schedule)
        monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "off")
        off = pmt.MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                                schedule=schedule)
        monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE")
        assert _lowered(unset, dx) == _lowered(off, dx)
        assert np.array_equal(np.asarray(unset.matvec(dx).asarray()),
                              np.asarray(off.matvec(dx).asarray()))


def test_tune_off_bit_identical_fft(monkeypatch):
    dims = (16, 8)
    unset = pmt.MPIFFT2D(dims)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "off")
    off = pmt.MPIFFT2D(dims)
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE")
    x = np.arange(int(np.prod(dims)), dtype=np.float64)
    dx = DistributedArray.to_dist(x, local_shapes=unset.model_local_shapes)
    assert _lowered(unset, dx) == _lowered(off, dx)


def test_tune_off_bit_identical_blockdiag(rng, monkeypatch):
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((4, 4)) for _ in range(8)]
    unset = pmt.MPIBlockDiag([MatrixMult(m) for m in mats])
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "off")
    off = pmt.MPIBlockDiag([MatrixMult(m) for m in mats])
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE")
    dx = DistributedArray.to_dist(rng.standard_normal(32))
    assert _lowered(unset, dx) == _lowered(off, dx)
    assert unset._normal_path is None and off._normal_path is None


def test_tune_off_bit_identical_derivative(monkeypatch):
    unset = pmt.MPIFirstDerivative((32, 8))
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "off")
    off = pmt.MPIFirstDerivative((32, 8))
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE")
    dx = DistributedArray.to_dist(np.arange(32 * 8, dtype=np.float64))
    assert _lowered(unset, dx) == _lowered(off, dx)


# --------------------------------------------------- plan application
def test_seeded_cache_flips_schedule(rng, monkeypatch):
    """A cached plan is applied to the sentinel kwargs — and ONLY to
    the sentinel kwargs (explicit values always win)."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    A = rng.standard_normal((24, 16)).astype(np.float64)
    # defaults pick 'gather' here (test_overlap pins that); seed the
    # opposite so the flip proves the seam is live
    from pylops_mpi_tpu.parallel.mesh import default_mesh, best_grid_2d
    mesh = default_mesh()
    grid = best_grid_2d(int(mesh.devices.size))
    # mirror the operator's consult extras (incl. the serving-width
    # batch hint — keys gain |b{K} when PYLOPS_MPI_TPU_BATCH>1)
    from pylops_mpi_tpu.utils.deps import batch_default
    key = tplan.plan_key("matrixmult", (24, 16, 8), np.float64,
                         int(mesh.devices.size),
                         tuple(mesh.axis_names),
                         {"grid": grid, "batch": batch_default()})
    tcache.store(key, {"params": {"schedule": "stat_a",
                                  "overlap": "off"},
                       "provenance": "tuned"})
    op = pmt.MPIMatrixMult(A, 8, kind="summa", dtype=np.float64)
    assert op.schedule == "stat_a"
    assert tplan.applied_provenance("matrixmult") == "tuned"
    # explicit kwarg beats the tuned plan
    op2 = pmt.MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                            schedule="gather")
    assert op2.schedule == "gather"
    # numerics unaffected by the flip
    X = rng.standard_normal((16, 8))
    dx = DistributedArray.to_dist(X.ravel())
    np.testing.assert_allclose(
        np.asarray(op.matvec(dx).asarray()).reshape(24, 8), A @ X,
        rtol=1e-10, atol=1e-12)


def test_env_pin_beats_tuned_plan(rng, monkeypatch):
    """An explicit PYLOPS_MPI_TPU_OVERLAP=on|off is user intent: a
    cached plan must not override it (same rule as explicit kwargs)."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_OVERLAP", "on")
    from pylops_mpi_tpu.parallel.mesh import default_mesh, best_grid_2d
    mesh = default_mesh()
    grid = best_grid_2d(int(mesh.devices.size))
    from pylops_mpi_tpu.utils.deps import batch_default
    key = tplan.plan_key("matrixmult", (24, 16, 8), np.float64,
                         int(mesh.devices.size),
                         tuple(mesh.axis_names),
                         {"grid": grid, "batch": batch_default()})
    tcache.store(key, {"params": {"schedule": "gather",
                                  "overlap": "off"}})
    A = rng.standard_normal((24, 16))
    op = pmt.MPIMatrixMult(A, 8, kind="summa", dtype=np.float64)
    assert op.overlap is True  # env pin survived the plan's "off"
    assert op.schedule == "gather"  # schedule sentinel still filled


def test_invalid_cached_params_fall_back(monkeypatch):
    """A cache entry whose params fail space validation (stale axis
    value after a code change) is a logged miss, never applied."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    key = tplan.plan_key("stack", (64, 8), np.float32, 8, ("sp",))
    tcache.store(key, {"params": {"overlap": "sideways"}})
    p = tplan.get_plan("stack", shape=(64, 8), dtype=np.float32,
                       n_dev=8, axes=("sp",))
    assert p is not None and p.provenance == "costmodel"
    assert p.get("overlap") in ("on", "off")
    assert _events("tuning.cache_error")


def test_costmodel_pick_matches_defaults_on_cpu(monkeypatch):
    """The analytic seed must reproduce today's defaults (overlap off
    on the CPU sim, fused normal path, env-default schedule) — the
    whole point of cost-model seeding."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    for op, shape, extra in (("stack", (64, 8), None),
                             ("derivative", (32, 8), None),
                             ("halo", (32, 8), None)):
        p = tplan.get_plan(op, shape=shape, n_dev=8, axes=("sp",),
                           extra=extra)
        assert p.provenance == "costmodel"
        assert p.get("overlap") == "off", op
    p = tplan.get_plan("blockdiag", shape=(256, 256), n_dev=8,
                       extra={"fused_available": True,
                              "a_bytes": 256 * 256 * 4.0})
    assert p.get("normal_path") == "fused"


def test_blockdiag_normal_path_kwarg(rng):
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((4, 4)).astype(np.float32)
            for _ in range(8)]
    forced = pmt.MPIBlockDiag([MatrixMult(m) for m in mats],
                              normal_path="two_sweep")
    assert forced.has_fused_normal is False
    with pytest.raises(ValueError, match="normal_path"):
        pmt.MPIBlockDiag([MatrixMult(m) for m in mats],
                         normal_path="warp")
    # two_sweep still computes the correct normal product
    dx = DistributedArray.to_dist(
        rng.standard_normal(32).astype(np.float32))
    u, q = forced.normal_matvec(dx)
    dense = np.zeros((32, 32), dtype=np.float32)
    for i, m in enumerate(mats):
        dense[4 * i:4 * i + 4, 4 * i:4 * i + 4] = m
    x = np.asarray(dx.asarray())
    np.testing.assert_allclose(np.asarray(u.asarray()),
                               dense.T @ (dense @ x), rtol=2e-4)


# ------------------------------------------------------ cache robustness
def test_cache_corrupt_file_falls_back(tmp_path, monkeypatch):
    path = tmp_path / "tc.json"
    path.write_text("{ this is not json")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_CACHE", str(path))
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    p = tplan.get_plan("stack", shape=(64, 8), n_dev=8, axes=("sp",))
    assert p is not None and p.provenance == "costmodel"
    evs = _events("tuning.cache_error")
    assert evs and "unreadable" in evs[0]["args"]["why"]


def test_cache_truncated_file_falls_back(tmp_path, monkeypatch):
    path = tmp_path / "tc.json"
    full = json.dumps({"schema": tcache.SCHEMA_VERSION,
                       "plans": {"k": {"params": {"overlap": "on"}}}})
    path.write_text(full[:len(full) // 2])
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_CACHE", str(path))
    assert tcache.load_plans() == {}
    # and a store() over the truncated file heals it atomically
    tcache.store("k2", {"params": {"overlap": "off"}})
    tcache.clear_memory()
    assert tcache.load_plans()["k2"]["params"] == {"overlap": "off"}
    doc = json.loads(path.read_text())
    assert doc["schema"] == tcache.SCHEMA_VERSION


def test_cache_schema_mismatch_falls_back(tmp_path, monkeypatch):
    path = tmp_path / "tc.json"
    path.write_text(json.dumps(
        {"schema": tcache.SCHEMA_VERSION + 99,
         "plans": {"k": {"params": {"overlap": "on"}}}}))
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_CACHE", str(path))
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    assert tcache.load_plans() == {}
    evs = _events("tuning.cache_error")
    assert evs and "schema" in evs[0]["args"]["why"]


def test_cache_cross_process_roundtrip(tmp_path, monkeypatch):
    """Write in a subprocess (the offline-CLI pattern), read in the
    parent — the persistence contract the harvest ladder relies on."""
    path = tmp_path / "tc.json"
    code = (
        "import os; os.environ['PYLOPS_MPI_TPU_TUNE_CACHE'] = %r\n"
        "from pylops_mpi_tpu.tuning import cache\n"
        "cache.store('xkey', {'params': {'overlap': 'on'},"
        " 'provenance': 'tuned'})\n" % str(path))
    env = dict(os.environ, PYLOPS_MPI_TPU_PLATFORM="cpu",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_CACHE", str(path))
    tcache.clear_memory()
    entry = tcache.lookup("xkey")
    assert entry and entry["params"] == {"overlap": "on"}


def test_cache_two_concurrent_writers_lose_nothing(tmp_path):
    """ISSUE 6 satellite: two PROCESSES hammering ``store()`` on the
    same cache file concurrently (the offline CLI racing a live
    auto-tuning session). The flock-serialized read-merge-write plus
    pid-suffixed temp staging must keep the file valid at all times
    and lose NO entry from either writer."""
    path = tmp_path / "race.json"
    n = 20
    code = (
        "import os, sys\n"
        "os.environ['PYLOPS_MPI_TPU_TUNE_CACHE'] = %r\n"
        "from pylops_mpi_tpu.tuning import cache\n"
        "tag = sys.argv[1]\n"
        "for i in range(%d):\n"
        "    cache.store(f'{tag}:{i}', {'params': {'i': i},"
        " 'provenance': tag})\n" % (str(path), n))
    env = dict(os.environ, PYLOPS_MPI_TPU_PLATFORM="cpu",
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", code, tag],
                              env=env, cwd=ROOT,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for tag in ("alpha", "beta")]
    for p in procs:
        _, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
    plans = tcache.load_plans(str(path))
    expected = {f"{tag}:{i}" for tag in ("alpha", "beta")
                for i in range(n)}
    assert expected.issubset(plans), sorted(expected - set(plans))
    # staging temp files are cleaned up; only the cache + lock remain
    leftovers = [f for f in os.listdir(tmp_path)
                 if f.startswith(".tune_cache_")]
    assert leftovers == []


# ----------------------------------------------------- search machinery
def _fake_factory(times):
    """Factory whose candidates 'run' for a scripted duration."""
    def factory(params):
        dt = times[params["overlap"]]

        def apply():
            time.sleep(dt)
            return None
        return apply
    return factory


def _stack_ctx():
    return {"op": "stack", "shape": (64, 8), "dtype": np.float32,
            "n_dev": 8, "axes": ("sp",), "platform": "cpu",
            "chip": "cpu", "extra": {}}


def test_measure_candidates_picks_measured_winner(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    sp = tspace.space_for("stack")
    # the non-default candidate is 4x faster: must win despite the
    # cost seed preferring 'off' on cpu
    params, trials = tsearch.measure_candidates(
        sp, _stack_ctx(), _fake_factory({"off": 0.04, "on": 0.01}),
        repeats=2)
    assert params == {"overlap": "on"}
    assert len(_events("tuning.trial")) == len(trials) == 2
    assert _events("tuning.winner")


def test_measure_candidates_hysteresis_keeps_default():
    sp = tspace.space_for("stack")
    # 1% faster is within the 2% margin: default stays
    params, _ = tsearch.measure_candidates(
        sp, _stack_ctx(), _fake_factory({"off": 0.0300, "on": 0.0297}),
        repeats=2)
    assert params == {"overlap": "off"}


def test_search_budget_exhaustion_skips():
    """A zero-second budget skips every trial (DeadlineRunner window
    semantics) — tuning can never eat a harvest window."""
    from pylops_mpi_tpu.diagnostics.profiler import (DeadlineRunner,
                                                     STAGE_BUDGETS)
    assert "tune" in STAGE_BUDGETS  # the central budget row exists
    sp = tspace.space_for("stack")
    runner = DeadlineRunner(deadline_ts=time.time() - 1, min_stage_s=1)
    params, trials = tsearch.measure_candidates(
        sp, _stack_ctx(), _fake_factory({"off": 0.01, "on": 0.01}),
        runner=runner, budget_s=10)
    assert params is None
    assert all(t["skipped"] for t in trials)


def test_auto_measures_then_replays_without_trials(tmp_path, monkeypatch):
    """The acceptance pin: a plan banked by a measured search is
    replayed from the cache file with ZERO tuning.trial events."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "auto")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_CACHE",
                       str(tmp_path / "tc.json"))
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE_BUDGET", "60")
    factory = _fake_factory({"off": 0.03, "on": 0.005})
    p1 = tplan.get_plan("stack", shape=(64, 8), dtype=np.float32,
                        n_dev=8, axes=("sp",), factory=factory)
    assert p1.provenance == "tuned"
    assert p1.get("overlap") == "on"
    assert len(_events("tuning.trial")) == 2  # it DID measure
    # second process (simulated: fresh memory, same file): replay
    tcache.clear_memory()
    trace.clear_events()
    p2 = tplan.get_plan("stack", shape=(64, 8), dtype=np.float32,
                        n_dev=8, axes=("sp",), factory=factory)
    assert p2.provenance == "tuned" and p2.params == p1.params
    assert len(_events("tuning.trial")) == 0  # zero timing trials
    assert any(e["args"].get("replay")
               for e in _events("tuning.plan"))


def test_shape_bucketing():
    assert tplan.shape_bucket((4000, 4096, 60)) == (4096, 4096, 64)
    k1 = tplan.plan_key("matrixmult", (4000, 4000, 60), np.float32, 8,
                        ("sp",))
    k2 = tplan.plan_key("matrixmult", (4096, 4096, 64), np.float32, 8,
                        ("sp",))
    assert k1 == k2
    assert k1 != tplan.plan_key("matrixmult", (4096, 4096, 64),
                                np.float32, 4, ("sp",))


def test_plan_key_batch_axis():
    """batch=1 (and absent) keep the historical key — existing caches
    stay valid; K>1 forks the key with a |b{K} suffix."""
    base = tplan.plan_key("matrixmult", (64, 64, 8), np.float32, 8,
                          ("sp",))
    k1 = tplan.plan_key("matrixmult", (64, 64, 8), np.float32, 8,
                        ("sp",), {"batch": 1})
    k16 = tplan.plan_key("matrixmult", (64, 64, 8), np.float32, 8,
                         ("sp",), {"batch": 16})
    assert k1 == base
    assert k16 != base and k16.endswith("|b16")


# ------------------------------------------------------- knob registry
def test_knob_registry_covers_every_package_read():
    """Grep the package for PYLOPS_MPI_TPU_* reads; every knob must
    have a registry row (utils/deps.py KNOBS) — the satellite that
    replaces per-PR ad-hoc knob lists."""
    from pylops_mpi_tpu.utils.deps import knob_names
    registered = set(knob_names())
    found = set()
    pkg = os.path.join(ROOT, "pylops_mpi_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                found.update(re.findall(r"PYLOPS_MPI_TPU_[A-Z0-9_]+",
                                        f.read()))
    # names that appear only as prose prefixes, not knobs
    found -= {"PYLOPS_MPI_TPU_"}
    missing = sorted(found - registered)
    assert not missing, (
        f"env knobs read in the package but missing from "
        f"utils/deps.py KNOBS: {missing}")


def test_knob_table_rendered_in_docs():
    from pylops_mpi_tpu.utils.deps import knob_names, knob_table_markdown
    with open(os.path.join(ROOT, "docs", "tpu.md")) as f:
        doc = f.read()
    for name in knob_names():
        assert name in doc, f"{name} missing from docs/tpu.md"
    assert knob_table_markdown().splitlines()[0].startswith("| knob")


# --------------------------------------------- roofline VMEM re-bucket
def test_roofline_rebuckets_vmem_regime():
    """Regression for the VERDICT round-5 misattribution: 1261 GB/s
    'measured' against an 819 GB/s v5e HBM peak must re-bucket to the
    VMEM regime, never report >100% of HBM."""
    from pylops_mpi_tpu.diagnostics import costmodel
    peaks = {"flops": 197e12 / 6, "hbm_gbps": 819.0, "ici_gbps": 200.0}
    hbm_bytes = 1e9  # per apply
    measured_s = hbm_bytes / (1261.0 * 1e9)  # implies 1261 GB/s
    rl = costmodel.roofline(
        costmodel.OpCost(flops=1e9, hbm_bytes=hbm_bytes), peaks,
        measured_s=measured_s)
    assert rl["regime"] == "vmem"
    assert rl["implied_hbm_gbps"] == pytest.approx(1261.0, abs=1.0)
    assert "hbm_pct" not in rl
    assert rl["bound"] != "hbm"
    # below the peak: honest hbm_pct, no re-bucket
    rl2 = costmodel.roofline(
        costmodel.OpCost(flops=1e9, hbm_bytes=hbm_bytes), peaks,
        measured_s=hbm_bytes / (400.0 * 1e9))
    assert rl2["regime"] == "hbm"
    assert rl2["hbm_pct"] == pytest.approx(100 * 400 / 819, abs=0.5)


def test_roofline_unmeasured_unchanged():
    from pylops_mpi_tpu.diagnostics import costmodel
    rl = costmodel.roofline(costmodel.OpCost(flops=1e9, hbm_bytes=1e9),
                            {"flops": 1e12, "hbm_gbps": 100.0})
    assert "regime" not in rl and rl["bound"] == "hbm"


# ------------------------------------------------------------- offline CLI
def test_cli_defaults_sweep_banks_cache(tmp_path):
    """`python -m pylops_mpi_tpu.tuning --defaults` banks cost-model
    plans (zero trials) into the named artifact — the cheap pre-seed
    path the CI tuning leg uses before measuring anything."""
    out = tmp_path / "seed.json"
    env = dict(os.environ, PYLOPS_MPI_TPU_PLATFORM="cpu",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("PYLOPS_MPI_TPU_TUNE", None)
    r = subprocess.run(
        [sys.executable, "-m", "pylops_mpi_tpu.tuning", "--defaults",
         "--quick", "--family", "stack", "--family", "derivative",
         "--out", str(out)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["bench"] == "tune_sweep"
    fams = {p["family"] for p in summary["plans"]}
    assert fams == {"stack", "derivative"}
    assert all(p["provenance"] == "costmodel"
               for p in summary["plans"])
    doc = json.loads(out.read_text())
    assert doc["schema"] == tcache.SCHEMA_VERSION and doc["plans"]
