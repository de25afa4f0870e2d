"""Always-on solve service (ISSUE 12): warm pool, admission queue,
continuous batcher, durable spool, and the supervised serve-forever
deployment.

Quick tests cover the packing edge cases the ISSUE pins — ragged final
batch, deadline-forced undersized dispatch, reject-on-full, poisoned
column isolated, crash-mid-batch re-enqueue idempotency — plus the
satellite seams (batched-solve cache knob/counters, plan-cache width
consult, histogram quantiles, drain plumbing). The two ``slow`` tests
are the acceptance pins: 32 concurrently-enqueued requests bit-for-bit
against sequential oracles at >= 4x their throughput, and the
2-process supervised smoke that SIGSTOPs a worker mid-stream and still
loses zero requests (``tests/serving_worker.py``)."""

import os
import signal
import threading
import time

import numpy as np
import pytest

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, serving
from pylops_mpi_tpu.diagnostics import metrics, trace
from pylops_mpi_tpu.diagnostics.profiler import STAGE_BUDGETS, stage_budget
from pylops_mpi_tpu.ops.local import MatrixMult
from pylops_mpi_tpu.resilience import elastic
from pylops_mpi_tpu.serving import (AdmissionQueue, Dispatcher, FamilySpec,
                                    QueueFull, SolveDaemon, WarmPool,
                                    bucket_for, k_buckets, pack)
from pylops_mpi_tpu.serving import spool
from pylops_mpi_tpu.serving.queue import SolveRequest
from pylops_mpi_tpu.solvers import batched_cache_info, batched_solve
from pylops_mpi_tpu.solvers.basic import _FUSED_CACHE
from pylops_mpi_tpu.solvers.block import _BATCHED_CACHE
from pylops_mpi_tpu.utils.deps import KNOBS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRUB = ("PYLOPS_MPI_TPU_SERVE_QUEUE", "PYLOPS_MPI_TPU_SERVE_WINDOW_MS",
          "PYLOPS_MPI_TPU_SERVE_K_BUCKETS",
          "PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT",
          "PYLOPS_MPI_TPU_BATCHED_CACHE", "PYLOPS_MPI_TPU_METRICS",
          "PYLOPS_MPI_TPU_GUARDS", "PYLOPS_MPI_TPU_RETRIES")


@pytest.fixture(autouse=True)
def _clean_serving_env(monkeypatch):
    for name in _SCRUB:
        monkeypatch.delenv(name, raising=False)
    metrics.clear_metrics()
    trace.clear_events()
    elastic.reset_drain()
    yield
    metrics.clear_metrics()
    trace.clear_events()
    elastic.reset_drain()


def _make_family(rng, name="fam", solver="cg", nblk=4, n=12,
                 niter=20, tol=0.0):
    mats = []
    for _ in range(nblk):
        m = rng.standard_normal((n, n)).astype(np.float32)
        mats.append(np.eye(n, dtype=np.float32) * 4 + 0.3 * (m + m.T))
    Op = pmt.MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    return FamilySpec(name=name, operator=Op, solver=solver,
                      niter=niter, tol=tol)


def _oracle(spec, y):
    yd = DistributedArray(global_shape=y.shape[0], dtype=np.float32)
    yd[:] = y
    if spec.solver == "cg":
        x, _, _ = pmt.cg(spec.operator, yd, niter=spec.niter,
                         tol=spec.tol)
    else:
        x, *_ = pmt.cgls(spec.operator, yd, niter=spec.niter,
                         damp=spec.damp, tol=spec.tol)
    return np.asarray(x.array)


def _requests(family, Y):
    return [SolveRequest(f"r{j}", family, Y[:, j], None)
            for j in range(Y.shape[1])]


# ------------------------------------------------------- buckets / pack
def test_k_buckets_parsing(monkeypatch):
    assert k_buckets() == (1, 2, 4, 8, 16)
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_K_BUCKETS", "8, 2,junk,-3,8")
    assert k_buckets() == (2, 8)
    # a typo must not leave the pool bucketless
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_K_BUCKETS", "zero,,")
    assert k_buckets() == (1, 2, 4, 8, 16)


def test_bucket_for_rounds_up_and_saturates():
    bs = (1, 2, 4, 8, 16)
    assert bucket_for(1, bs) == 1
    assert bucket_for(3, bs) == 4
    assert bucket_for(16, bs) == 16
    assert bucket_for(99, bs) == 16       # overflow saturates at k_max


def test_pack_stacks_and_rejects_mixed(rng):
    Y = rng.standard_normal((24, 3)).astype(np.float32)
    reqs = _requests("fam", Y)
    Yp, bucket = pack(reqs, (1, 2, 4))
    np.testing.assert_array_equal(Yp, Y)
    assert bucket == 4
    reqs[1].family = "other"
    with pytest.raises(ValueError, match="one family per batch"):
        pack(reqs, (1, 2, 4))
    with pytest.raises(ValueError, match="empty batch"):
        pack([], (1, 2, 4))


def test_family_spec_validation(rng):
    with pytest.raises(ValueError, match="'cg' or 'cgls'"):
        _make_family(rng, solver="ista")
    pool = WarmPool(buckets=(2,))
    spec = _make_family(rng)
    pool.register(spec)
    with pytest.raises(ValueError, match="already registered"):
        pool.register(spec)
    with pytest.raises(KeyError, match="unknown operator family"):
        pool.family("nope")
    with pytest.raises(ValueError, match="expects data length"):
        pool.solve("fam", np.zeros(7, dtype=np.float32))
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        pool.solve("fam", np.zeros((spec.nrows, 3), dtype=np.float32))


# ------------------------------------------------------------ warm pool
def test_pool_padded_solve_matches_oracle(rng):
    """A 3-wide fill padded into the 4-bucket program returns each
    column's single-RHS answer (zero-pad exact by per-column freeze)."""
    pool = WarmPool(buckets=(4,))
    spec = pool.register(_make_family(rng))
    Y = rng.standard_normal((spec.nrows, 3)).astype(np.float32)
    out = pool.solve("fam", Y)
    assert out.x.shape == (spec.nrows, 3)
    assert out.k == 3 and out.bucket == 4
    assert len(out.statuses) == 3
    for j in range(3):
        np.testing.assert_allclose(out.x[:, j], _oracle(spec, Y[:, j]),
                                   rtol=0, atol=1e-5)


def test_prewarm_compiles_before_traffic(rng):
    """Prewarm's zero-RHS solve banks the fused executable: the first
    real request adds NO new cache entries (same operator instance,
    same (family, bucket) program)."""
    pmt.clear_fused_cache()
    pool = WarmPool(buckets=(2,))
    spec = pool.register(_make_family(rng, solver="cgls"))
    report = pool.prewarm()
    assert report == {"fam": [2]}
    assert ("fam", 2) in pool.warmed
    keys = set(_FUSED_CACHE)
    assert keys, "prewarm compiled nothing"
    out = pool.solve("fam", rng.standard_normal(
        (spec.nrows, 1)).astype(np.float32))
    assert out.bucket == 2
    assert set(_FUSED_CACHE) == keys, \
        "first request recompiled despite prewarm"


def test_prewarm_without_widths_compiles_every_bucket(rng):
    """No ``widths``: prewarm compiles every configured bucket. Given
    widths round up to their buckets and are compiled alone."""
    pool = WarmPool(buckets=(1, 2, 4))
    pool.register(_make_family(rng))
    assert pool.prewarm() == {"fam": [1, 2, 4]}
    assert {("fam", b) for b in (1, 2, 4)} <= pool.warmed
    pool = WarmPool(buckets=(2, 4))
    pool.register(_make_family(rng))
    assert pool.prewarm(widths=[3]) == {"fam": [4]}
    assert pool.warmed == {("fam", 4)}


# ---------------------------------------------------- admission + queue
def test_reject_on_full_backpressure(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    q = AdmissionQueue(bound=2)
    y = np.zeros(4, dtype=np.float32)
    q.submit("fam", y)
    q.submit("fam", y)
    with pytest.raises(QueueFull, match="bound 2"):
        q.submit("fam", y)
    assert q.submitted == 2 and q.rejected == 1
    snap = metrics.snapshot()
    assert snap["counters"]["serve.rejects"] == 1
    assert snap["counters"]["serve.requests"] == 2
    assert snap["gauges"]["serve.queue.depth"] == 2


def test_queue_bound_knob(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_QUEUE", "3")
    assert AdmissionQueue().bound == 3
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_QUEUE", "junk")
    assert AdmissionQueue().bound == 1024


def test_draining_queue_rejects_new_admissions():
    q = AdmissionQueue(bound=10)
    q.submit("fam", np.zeros(4, dtype=np.float32))
    q.start_drain()
    with pytest.raises(QueueFull, match="draining"):
        q.submit("fam", np.zeros(4, dtype=np.float32))
    # already-queued work still dispatches
    batch, forced = q.collect(k_max=4, window_s=0.0)
    assert len(batch) == 1 and not forced


def test_collect_takes_oldest_family_fifo():
    q = AdmissionQueue(bound=10)
    for j in range(3):
        q.submit("a", np.zeros(4, dtype=np.float32))
    q.submit("b", np.zeros(4, dtype=np.float32))
    batch, _ = q.collect(k_max=2, window_s=0.0)
    assert [r.family for r in batch] == ["a", "a"]
    assert [r.request_id for r in batch] == ["r0", "r1"]
    # family b stays queued behind the remaining a
    batch, _ = q.collect(k_max=2, window_s=0.0)
    assert [r.family for r in batch] == ["a"]
    batch, _ = q.collect(k_max=2, window_s=0.0)
    assert [r.family for r in batch] == ["b"]


# ----------------------------------------------------- daemon dispatch
def test_ragged_final_batch_pads_and_matches_oracle(rng, monkeypatch):
    """5 requests through a 4-bucket daemon: one full batch + a ragged
    final batch of 1 padded to 4 — every answer the single-RHS
    oracle's."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    pool = WarmPool(buckets=(4,))
    spec = pool.register(_make_family(rng))
    d = SolveDaemon(pool, window_s=0.15).start()
    try:
        Y = rng.standard_normal((spec.nrows, 5)).astype(np.float32)
        tickets = [d.submit("fam", Y[:, j]) for j in range(5)]
        res = [t.wait(timeout=120) for t in tickets]
    finally:
        assert d.drain()
    assert d.dispatcher.batches == 2
    assert d.dispatcher.solves == 5
    assert sorted(d.dispatcher.fill_samples) == [0.25, 1.0]
    assert res[4]["batch_k"] == 1 and res[4]["bucket"] == 4
    for j in range(5):
        np.testing.assert_allclose(res[j]["x"], _oracle(spec, Y[:, j]),
                                   rtol=0, atol=1e-5)
    st = d.stats()
    assert st["batches"] == 2 and st["solves"] == 5
    assert st["wait_p99_s"] >= st["wait_p50_s"] >= 0.0
    assert st["solves_per_sec"] > 0
    snap = metrics.snapshot()
    assert snap["counters"]["serve.solves"] == 5
    assert snap["histograms"]["serve.queue.wait_s"]["count"] == 5
    q = metrics.hist_quantiles("serve.queue.wait_s")
    assert q is not None and q["p99"] >= q["p50"]


def test_deadline_forces_undersized_dispatch(rng, monkeypatch):
    """3 requests with a near deadline in a 5s-window 8-bucket daemon:
    the batch goes out undersized BEFORE the window, inside the
    deadline."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    pool = WarmPool(buckets=(8,))
    spec = pool.register(_make_family(rng))
    pool.prewarm()                       # solves are ms once warm
    d = SolveDaemon(pool, window_s=5.0).start()
    # a generous solve-time estimate widens the dispatch margin so the
    # forced dispatch happens well before the deadline (no skip race)
    d.dispatcher._ewma_wall = 0.2
    try:
        Y = rng.standard_normal((spec.nrows, 3)).astype(np.float32)
        deadline = time.time() + 1.0
        t0 = time.monotonic()
        tickets = [d.submit("fam", Y[:, j], deadline_ts=deadline)
                   for j in range(3)]
        res = [t.wait(timeout=30) for t in tickets]
        elapsed = time.monotonic() - t0
    finally:
        d.drain()
    assert elapsed < 4.0, "window expiry dispatched, not the deadline"
    assert d.dispatcher.forced == 1 and d.dispatcher.batches == 1
    assert res[0]["batch_k"] == 3 and res[0]["bucket"] == 8
    for j in range(3):
        np.testing.assert_allclose(res[j]["x"], _oracle(spec, Y[:, j]),
                                   rtol=0, atol=1e-5)
    assert metrics.snapshot()["counters"]["serve.deadline_forced"] == 1


def test_past_deadline_skips_batch_and_fails_tickets(rng, monkeypatch):
    """A batch whose deadline already passed is SKIPPED by the
    DeadlineRunner — tickets fail fast with the runner's reason
    instead of burning solver time."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    pool = WarmPool(buckets=(4,))
    pool.register(_make_family(rng))
    d = SolveDaemon(pool, window_s=5.0).start()
    try:
        t = d.submit("fam", np.ones(pool.family("fam").nrows,
                                    dtype=np.float32),
                     deadline_ts=time.time() - 5.0)
        with pytest.raises(RuntimeError, match="window exhausted"):
            t.wait(timeout=30)
    finally:
        d.drain()
    assert d.dispatcher.failed == 1
    assert metrics.snapshot()["counters"]["serve.deadline_missed"] == 1


def test_poisoned_column_isolated(rng, monkeypatch):
    """GUARDS=on serve: one tenant's NaN data breaks down its OWN
    column; batch-mates converge to the clean block solve's answers."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_GUARDS", "on")
    pool = WarmPool(buckets=(4,))
    spec = pool.register(_make_family(rng, niter=80, tol=1e-6))
    Y = rng.standard_normal((spec.nrows, 4)).astype(np.float32)
    clean = pool.solve("fam", Y)
    Yp = Y.copy()
    Yp[0, 1] = np.nan
    d = SolveDaemon(pool, window_s=0.5).start()
    try:
        tickets = [d.submit("fam", Yp[:, j]) for j in range(4)]
        res = [t.wait(timeout=120) for t in tickets]
    finally:
        d.drain()
    assert res[1]["status"] == "breakdown"
    for j in (0, 2, 3):
        assert res[j]["status"] == "converged"
        np.testing.assert_allclose(res[j]["x"], clean.x[:, j],
                                   rtol=0, atol=1e-5)


def test_stats_split_the_wait_and_the_stages(rng):
    """``admit_wait`` ends where the request leaves the queue, so it
    excludes the solve that ``wait`` includes; the six stages' seconds
    fit inside the daemon's lifetime."""
    spec = _make_family(rng, solver="cgls", niter=5)
    pool = WarmPool(buckets=(1, 4))
    pool.register(spec)
    daemon = SolveDaemon(pool)
    t0 = time.monotonic()
    daemon.start(prewarm=True)
    ys = rng.standard_normal((6, spec.nrows)).astype(np.float32)
    for t in [daemon.submit(spec.name, y) for y in ys]:
        t.wait(timeout=120)
    st = daemon.stats()
    life = time.monotonic() - t0
    daemon.drain(timeout=30)
    assert st["solves"] == 6
    assert 0 <= st["admit_wait_p50_s"] <= st["wait_p50_s"]
    assert st["admit_wait_p99_s"] <= st["wait_p99_s"]
    assert set(st["stage_s"]) == {"collect", "pack", "stage_in", "solve",
                                  "pull", "resolve"}
    assert all(v > 0 for v in st["stage_s"].values())
    assert sum(st["stage_s"].values()) <= life


def test_daemon_requires_start_and_drains_clean(rng):
    pool = WarmPool(buckets=(1,))
    pool.register(_make_family(rng))
    d = SolveDaemon(pool)
    with pytest.raises(RuntimeError, match="start"):
        d.submit("fam", np.zeros(48, dtype=np.float32))
    d.start()
    assert d.drain()                    # empty drain is clean
    with pytest.raises(RuntimeError, match="start"):
        d.submit("fam", np.zeros(48, dtype=np.float32))


# ------------------------------------------------------------- spool
def test_spool_roundtrip_and_claim_order(tmp_path, rng):
    root = str(tmp_path / "spool")
    y0 = rng.standard_normal(8).astype(np.float32)
    y1 = rng.standard_normal(8).astype(np.float32)
    r0 = spool.enqueue(root, "fam", y0, request_id="req0")
    time.sleep(0.02)                    # mtime-ordered claims
    spool.enqueue(root, "fam", y1, request_id="req1",
                  deadline_ts=123.0)
    assert spool.pending_count(root) == 2
    claims = spool.claim(root, limit=1)
    assert len(claims) == 1 and claims[0].request_id == "req0"
    assert claims[0].attempt == 0
    np.testing.assert_array_equal(claims[0].y, y0)
    assert spool.claimed_count(root) == 1
    x = rng.standard_normal(8).astype(np.float32)
    spool.complete(root, claims[0], x, iiter=7, status="converged")
    assert spool.claimed_count(root) == 0
    back = spool.read_result(root, r0)
    np.testing.assert_array_equal(back["x"], x)
    assert back["iiter"] == 7 and back["status"] == "converged"
    (c1,) = spool.claim(root, limit=4)
    assert c1.request_id == "req1" and c1.deadline_ts == 123.0
    spool.fail(root, c1, "boom")
    assert spool.claimed_count(root) == 0
    assert spool.result_ids(root) == ["req0"]


def test_spool_recover_is_idempotent(tmp_path, rng):
    """Crash-mid-batch recovery: claimed work returns to pending with
    the attempt bumped; a second sweep is a no-op; a claim whose
    result ALREADY landed (crash between bank and release) is released
    without re-enqueue."""
    root = str(tmp_path / "spool")
    y = rng.standard_normal(8).astype(np.float32)
    spool.enqueue(root, "fam", y, request_id="lost")
    spool.enqueue(root, "fam", y, request_id="banked")
    claims = {c.request_id: c for c in spool.claim(root, limit=2)}
    # "banked" got its result written, then the worker died before
    # releasing the claim
    spool.complete(root, claims["banked"], np.zeros(8), status="converged")
    # re-create the orphan claim state for "banked"? complete() already
    # released it — only "lost" is orphaned
    assert spool.claimed_count(root) == 1
    requeued, quarantined = spool.recover_claimed(root)
    assert (requeued, quarantined) == (1, 0)
    assert spool.pending_count(root) == 1
    # idempotent: a second sweep finds nothing claimed, moves nothing
    assert spool.recover_claimed(root) == (0, 0)
    assert spool.pending_count(root) == 1
    (c2,) = spool.claim(root, limit=1)
    assert c2.request_id == "lost" and c2.attempt == 1
    # result-already-exists path: claim released, not re-enqueued
    spool.complete(root, c2, np.ones(8))
    spool.enqueue(root, "fam", y, request_id="lost2")
    (c3,) = spool.claim(root, limit=1)
    spool.complete(root, c3, np.ones(8))
    # fabricate a stale claim file for an id whose result exists
    # (crash between result bank and claim release)
    import shutil
    stale = os.path.join(root, "claimed", "lost2.a0.npz")
    shutil.copy(os.path.join(root, "results", "lost2.npz"), stale)
    assert spool.recover_claimed(root) == (0, 0)
    assert not os.path.exists(stale)
    assert spool.pending_count(root) == 0


def test_spool_retry_budget_quarantines(tmp_path, rng, monkeypatch):
    """A request that keeps killing its worker is quarantined after
    the PR 6 retry budget instead of crash-looping the fleet."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_RETRIES", "1")  # 2 total attempts
    root = str(tmp_path / "spool")
    y = rng.standard_normal(8).astype(np.float32)
    spool.enqueue(root, "fam", y, request_id="killer")
    spool.claim(root, limit=1)
    assert spool.recover_claimed(root) == (1, 0)     # attempt 0 -> 1
    (c,) = spool.claim(root, limit=1)
    assert c.attempt == 1
    assert spool.recover_claimed(root) == (0, 1)     # budget exhausted
    assert spool.pending_count(root) == 0
    err = os.path.join(root, "failed", "killer.a1.npz.err")
    assert "retry budget exhausted" in open(err).read()


def test_spool_drain_marker(tmp_path):
    root = str(tmp_path / "spool")
    spool.init_spool(root)
    assert not spool.drain_requested(root)
    spool.request_drain(root)
    assert spool.drain_requested(root)


def test_spool_skips_foreign_files(tmp_path, rng):
    root = str(tmp_path / "spool")
    spool.init_spool(root)
    open(os.path.join(root, "pending", "README.txt"), "w").write("x")
    open(os.path.join(root, "pending", "noattempt.npz"), "w").write("x")
    spool.enqueue(root, "fam", rng.standard_normal(4), request_id="ok")
    claims = spool.claim(root, limit=10)
    assert [c.request_id for c in claims] == ["ok"]


# ------------------------------------------------------ drain plumbing
def test_process_drain_flag_and_sigterm_chain():
    assert not elastic.drain_requested()
    elastic.request_drain()
    assert elastic.drain_requested()
    elastic.reset_drain()
    prev_called = []
    handler_prev = signal.getsignal(signal.SIGTERM)
    try:
        signal.signal(signal.SIGTERM, lambda s, f: prev_called.append(s))
        assert elastic.install_sigterm_drain()
        assert elastic.install_sigterm_drain()    # idempotent
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)
        assert elastic.drain_requested()
        assert prev_called == [signal.SIGTERM]    # previous handler chained
    finally:
        signal.signal(signal.SIGTERM, handler_prev)
        elastic.reset_drain()


def test_install_sigterm_drain_off_main_thread_is_noop():
    out = []
    t = threading.Thread(
        target=lambda: out.append(elastic.install_sigterm_drain()))
    t.start()
    t.join()
    assert out == [False]


def test_worker_main_drains_on_spool_marker(rng, tmp_path):
    """The supervised replica end-to-end in-process: claims spooled
    requests, banks oracle-matching results, and exits on the DRAIN
    marker."""
    root = str(tmp_path / "spool")
    pool = WarmPool(buckets=(2,))
    spec = pool.register(_make_family(rng))
    Y = rng.standard_normal((spec.nrows, 3)).astype(np.float32)
    for j in range(3):
        spool.enqueue(root, "fam", Y[:, j], request_id=f"req{j}")
    spool.request_drain(root)
    solved = serving.worker_main(root, pool, prewarm=False,
                                 window_s=0.02)
    assert solved == 3
    assert spool.result_ids(root) == ["req0", "req1", "req2"]
    for j in range(3):
        res = spool.read_result(root, f"req{j}")
        np.testing.assert_allclose(res["x"], _oracle(spec, Y[:, j]),
                                   rtol=0, atol=1e-5)
    assert spool.pending_count(root) == 0
    assert spool.claimed_count(root) == 0


# ------------------------------------------------- satellite seams
def test_batched_cache_knob_and_counters(rng, monkeypatch):
    """Satellite 1: the batched_solve executable LRU reports hits and
    misses to the metrics registry, its capacity comes from
    PYLOPS_MPI_TPU_BATCHED_CACHE, and batched_cache_info() exposes the
    live contents."""
    from pylops_mpi_tpu.ops.fredholm import MPIFredholm1
    from pylops_mpi_tpu.distributedarray import Partition
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_BATCHED_CACHE", "1")
    _BATCHED_CACHE.clear()

    def factory(G):
        return MPIFredholm1(G, nz=2, dtype="float32")

    Gs = [(rng.standard_normal((8, 6, 6)) + 3 * np.eye(6)
           ).astype(np.float32) for _ in range(2)]
    ys = []
    for _ in range(2):
        y = DistributedArray(global_shape=8 * 6 * 2,
                             partition=Partition.BROADCAST,
                             dtype=np.float32)
        y[:] = rng.standard_normal(8 * 6 * 2).astype(np.float32)
        ys.append(y)

    batched_solve(factory, Gs, ys, solver="cg", niter=3, tol=0.0)
    batched_solve(factory, Gs, ys, solver="cg", niter=3, tol=0.0)
    snap = metrics.snapshot()
    assert snap["counters"]["solver.batched.cache.miss"] == 1
    assert snap["counters"]["solver.batched.cache.hit"] == 1
    info = batched_cache_info()
    assert info["size"] == 1 and info["max"] == 1
    assert info["families"] == [("cg", 3, 2, "MPIFredholm1")]
    # a different schedule evicts under the 1-entry bound
    batched_solve(factory, Gs, ys, solver="cg", niter=4, tol=0.0)
    info = batched_cache_info()
    assert info["size"] == 1
    assert info["families"] == [("cg", 4, 2, "MPIFredholm1")]
    _BATCHED_CACHE.clear()


def test_batched_cache_knob_malformed_falls_back(monkeypatch):
    from pylops_mpi_tpu.solvers.block import _batched_cache_max
    monkeypatch.setenv("PYLOPS_MPI_TPU_BATCHED_CACHE", "junk")
    assert _batched_cache_max() == 8
    monkeypatch.setenv("PYLOPS_MPI_TPU_BATCHED_CACHE", "0")
    assert _batched_cache_max() == 1


def test_hist_quantiles_window(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    assert metrics.hist_quantiles("nothing") is None
    for v in range(1, 101):
        metrics.observe("serve.queue.wait_s", float(v))
    q = metrics.hist_quantiles("serve.queue.wait_s")
    assert q["p50"] in (50.0, 51.0) and q["p99"] == 99.0  # nearest rank
    q = metrics.hist_quantiles("serve.queue.wait_s", qs=(0.0, 1.0))
    assert q["p0"] == 1.0 and q["p100"] == 100.0


def test_hist_quantiles_off_is_none():
    metrics.observe("serve.queue.wait_s", 1.0)
    assert metrics.hist_quantiles("serve.queue.wait_s") is None


def test_serve_knobs_registered_and_budgets_present():
    names = {k[0] for k in KNOBS}
    for knob in ("PYLOPS_MPI_TPU_SERVE_QUEUE",
                 "PYLOPS_MPI_TPU_SERVE_WINDOW_MS",
                 "PYLOPS_MPI_TPU_SERVE_K_BUCKETS",
                 "PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT",
                 "PYLOPS_MPI_TPU_BATCHED_CACHE"):
        assert knob in names, f"{knob} missing from deps.KNOBS"
    assert "serve_batch" in STAGE_BUDGETS
    assert "serve_smoke" in STAGE_BUDGETS
    assert stage_budget("serve_batch") == 120


def test_window_knob_parsing(monkeypatch):
    from pylops_mpi_tpu.serving.queue import batch_window_s
    assert batch_window_s() == pytest.approx(0.010)
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_WINDOW_MS", "250")
    assert batch_window_s() == pytest.approx(0.250)
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_WINDOW_MS", "-5")
    assert batch_window_s() == 0.0
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_WINDOW_MS", "junk")
    assert batch_window_s() == pytest.approx(0.010)


def test_drain_timeout_knob(monkeypatch):
    assert serving.drain_timeout_s() == 30.0
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT", "2.5")
    assert serving.drain_timeout_s() == 2.5
    monkeypatch.setenv("PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT", "junk")
    assert serving.drain_timeout_s() == 30.0


# ------------------------------------------------- acceptance (slow)
def _flagship_pool():
    """EXACTLY tests/serving_worker.py's build (seed 3): the bench
    flagship block-diagonal problem, CGLS, tol=0 (full schedule —
    the bit-for-bit setting)."""
    import tests.serving_worker as sw
    return sw.build_pool()


@pytest.mark.slow
def test_32_requests_bit_for_bit_and_4x_throughput(rng):
    """ISSUE 12 acceptance: 32 concurrently-enqueued single-RHS
    requests through the packed K=16 daemon match their sequential
    fused-solve oracles BIT-FOR-BIT (tol=0 pins both sides to the
    same schedule; zero-pad exact by per-column freeze), at >= 4x the
    sequential throughput on the 8-device CPU sim."""
    pool = _flagship_pool()
    spec = pool.family("flagship")
    N = spec.nrows
    Y = rng.standard_normal((N, 32)).astype(np.float32)

    # sequential oracles + their timed throughput (one warm solve
    # first so compile is excluded from the timed loop)
    _oracle(spec, Y[:, 0])
    t0 = time.perf_counter()
    oracles = []
    for j in range(32):
        oracles.append(_oracle(spec, Y[:, j]))
    t_seq = time.perf_counter() - t0
    seq_rate = 32 / t_seq

    pool.prewarm(widths=[16])
    d = SolveDaemon(pool, window_s=0.25).start()
    try:
        tickets = [None] * 32

        def _enqueue(j):
            tickets[j] = d.submit("flagship", Y[:, j])

        threads = [threading.Thread(target=_enqueue, args=(j,))
                   for j in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        res = [tickets[j].wait(timeout=300) for j in range(32)]
    finally:
        assert d.drain()

    for j in range(32):
        np.testing.assert_array_equal(res[j]["x"], oracles[j])
    st = d.stats()
    assert st["solves"] == 32 and st["failed"] == 0
    packed_rate = st["solves_per_sec"]
    assert packed_rate >= 4 * seq_rate, \
        (f"packed {packed_rate:.1f}/s < 4x sequential "
         f"{seq_rate:.1f}/s (batches={st['batches']}, "
         f"fill={st['fill_mean']:.2f})")


@pytest.mark.slow
def test_serve_forever_smoke_survives_worker_kill(tmp_path, rng):
    """ISSUE 12 kill-a-worker smoke: 2 supervised serving replicas on
    one spool, 32 spooled requests, SIGSTOP worker 1 mid-stream — the
    supervisor classifies the stale heartbeat, the relaunch hook
    re-enqueues its in-flight claims, and all 32 results land and
    match the oracle: zero requests lost."""
    spool_dir = str(tmp_path / "spool")
    logdir = str(tmp_path / "logs")
    N = 8 * 48
    Y = rng.standard_normal((N, 32)).astype(np.float32)
    ids = [f"req{j:02d}" for j in range(32)]
    # stream the requests in (at most 8 outstanding) instead of
    # pre-loading all 32, so the SIGSTOP provably lands mid-stream
    spool.init_spool(spool_dir)
    enq = {"n": 0}

    def _feed(done):
        while enq["n"] < 32 and enq["n"] - done < 8:
            j = enq["n"]
            spool.enqueue(spool_dir, "flagship", Y[:, j],
                          request_id=ids[j])
            enq["n"] += 1

    _feed(0)

    env = {"PYLOPS_SERVE_SPOOL": spool_dir,
           "PYLOPS_MPI_TPU_METRICS": "on",
           # rounds of 4 so the SIGSTOP lands mid-stream
           "PYLOPS_MPI_TPU_SERVE_K_BUCKETS": "4",
           # workers pin their own 8 virtual devices
           "XLA_FLAGS": " ".join(
               f for f in os.environ.get("XLA_FLAGS", "").split()
               if "force_host_platform_device_count" not in f)}
    stopped = []
    drained = []

    def on_poll(attempt, workers):
        done = len(spool.result_ids(spool_dir))
        _feed(done)
        if attempt == 0 and not stopped and done >= 4 \
                and len(workers) > 1 and workers[1].alive():
            workers[1].proc.send_signal(signal.SIGSTOP)
            stopped.append(done)
        if not drained and enq["n"] >= 32 and done >= 32:
            spool.request_drain(spool_dir)
            drained.append(done)

    budget = stage_budget("serve_smoke")
    r = serving.serve_job(
        [os.path.join(ROOT, "tests", "serving_worker.py")], 2,
        spool_dir=spool_dir, max_relaunches=2,
        heartbeat_interval=0.4, stale_factor=2.0,
        on_poll=on_poll, job_timeout_s=budget, env=env, logdir=logdir)

    assert stopped, "SIGSTOP never fired (workers finished too fast?)"
    assert r.ok, (r.failures, {k: v[-2000:] for k, v in r.outputs.items()})
    assert r.attempts == 2
    assert r.failures[0].kind == "stale_heartbeat"
    assert r.failures[0].slot == 1

    # zero requests lost: every id has a banked, oracle-matching result
    assert spool.result_ids(spool_dir) == ids
    assert spool.pending_count(spool_dir) == 0
    assert spool.claimed_count(spool_dir) == 0
    assert not [n for n in os.listdir(os.path.join(spool_dir, "failed"))]
    pool = _flagship_pool()
    spec = pool.family("flagship")
    for j in range(32):
        res = spool.read_result(spool_dir, ids[j])
        assert res["status"] in ("converged", "maxiter")
        np.testing.assert_allclose(res["x"], _oracle(spec, Y[:, j]),
                                   rtol=0, atol=1e-5)
