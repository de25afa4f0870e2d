"""Serve-forever deployment: daemon facade, worker loop, supervisor.

Three layers, innermost first:

- :class:`SolveDaemon` — one process's always-on solve service: an
  :class:`~.queue.AdmissionQueue` + :class:`~.queue.Dispatcher` over a
  :class:`~.engine.WarmPool`. ``submit()`` returns a
  :class:`~.queue.Ticket`; ``stats()`` is the backpressure report;
  ``drain()`` stops admission, finishes in-flight batches, and joins
  the dispatcher within ``PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT``.
- :func:`worker_main` — the supervised replica: heartbeats
  (:func:`~pylops_mpi_tpu.resilience.elastic.maybe_start_heartbeat`,
  beats carry the live metrics registry), SIGTERM routed to a graceful
  drain, and a claim→solve→bank loop against the durable
  :mod:`~.spool`. Replicas are INDEPENDENT — each owns its local
  devices and compiled pool; scaling out is adding claimants on the
  shared spool, with rename atomicity as the only coordination.
- :func:`serve_job` — grows the PR 7 supervisor from run-one-job into
  serve-forever: ``launch_job`` with an ``on_relaunch`` hook that
  sweeps the dead attempt's claimed-but-unfinished requests back to
  pending (bounded by the retry budget) BEFORE the relaunch, so a
  crashed worker's in-flight batch is lost to nobody. Worker crash →
  classify → kill attempt → recover claims → relaunch on surviving
  slots, exactly the chaos-leg lifecycle, now with zero dropped
  requests.

Stopping a deployment is a drain, not a kill: SIGTERM (or the spool's
DRAIN marker) stops admission/claiming; workers finish what they hold
and exit 0; the supervisor sees clean exits and reports ``ok=True``.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional, Sequence

import numpy as np

from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace
from .engine import WarmPool
from .queue import AdmissionQueue, Dispatcher, Ticket
from . import spool as _spool

__all__ = ["drain_timeout_s", "SolveDaemon", "worker_main", "serve_job"]


def drain_timeout_s() -> float:
    """``PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT`` graceful-drain bound in
    seconds (default 30.0, floored at 0)."""
    try:
        v = float(os.environ.get("PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT",
                                 "30"))
    except ValueError:
        v = 30.0
    return max(0.0, v)


class SolveDaemon:
    """One process's always-on solve service (see module docstring).

    ``prewarm=True`` compiles the pool's (family, bucket) programs
    before :meth:`start` returns, so the first request never pays
    compile latency."""

    def __init__(self, pool: WarmPool, *,
                 window_s: Optional[float] = None,
                 queue_bound: Optional[int] = None):
        self.pool = pool
        self.queue = AdmissionQueue(bound=queue_bound)
        self.dispatcher = Dispatcher(pool, self.queue,
                                     window_s=window_s)
        self._started = False

    def start(self, prewarm: bool = False) -> "SolveDaemon":
        if prewarm:
            self.pool.prewarm()
        if not self._started:
            self.dispatcher.start()
            self._started = True
            _trace.event("serve.daemon_start", cat="serving",
                         families=list(self.pool.families()),
                         buckets=list(self.pool.buckets))
        return self

    def submit(self, family: str, y: np.ndarray,
               deadline_ts: Optional[float] = None,
               request_id: Optional[str] = None) -> Ticket:
        """Admit one single-RHS request (raises
        :class:`~.queue.QueueFull` past the bound — backpressure)."""
        if not self._started:
            raise RuntimeError("SolveDaemon.start() before submit()")
        return self.queue.submit(family, y, deadline_ts=deadline_ts,
                                 request_id=request_id)

    def stats(self) -> Dict:
        return self.dispatcher.stats()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful stop: refuse new admissions, wait for the queue to
        empty and in-flight batches to resolve (bounded by ``timeout``,
        default the drain knob), then join the dispatcher. True when
        fully drained in time."""
        timeout = drain_timeout_s() if timeout is None else timeout
        self.queue.start_drain()
        end = time.monotonic() + timeout
        drained = self.queue.drain_empty(timeout=timeout)
        while drained and not self.dispatcher.idle():
            if time.monotonic() >= end:
                drained = False
                break
            time.sleep(0.01)
        self.dispatcher.stop()
        self._started = False
        _trace.event("serve.daemon_drain", cat="serving",
                     drained=drained, **self.stats())
        return drained


def worker_main(spool_dir: str, pool: WarmPool, *,
                poll_s: float = 0.02,
                window_s: Optional[float] = None,
                prewarm: bool = True,
                idle_exit_s: Optional[float] = None) -> int:
    """Supervised serve-forever replica over a durable spool.

    Claims up to ``k_max`` pending requests per round, runs them
    through this process's :class:`SolveDaemon` (so admission-window /
    deadline semantics apply), banks each result, and releases the
    claims. Exits 0 when a drain is requested — SIGTERM
    (:func:`~pylops_mpi_tpu.resilience.elastic.install_sigterm_drain`)
    or the spool's DRAIN marker — and everything pending is done.
    ``idle_exit_s`` (tests) also exits after that long with no work
    and no drain. Returns the number of requests this worker solved.
    """
    from ..resilience import elastic
    _spool.init_spool(spool_dir)
    elastic.maybe_start_heartbeat()
    elastic.install_sigterm_drain()
    daemon = SolveDaemon(pool, window_s=window_s).start(prewarm=prewarm)
    solved = 0
    idle_since = time.monotonic()
    _metrics.set_gauge("serve.worker.up", 1)
    while True:
        draining = (elastic.drain_requested()
                    or _spool.drain_requested(spool_dir))
        claims = _spool.claim(spool_dir, daemon.pool.k_max)
        if not claims:
            if draining:
                break
            if idle_exit_s is not None and \
                    time.monotonic() - idle_since > idle_exit_s:
                break
            time.sleep(poll_s)
            continue
        idle_since = time.monotonic()
        tickets = [(c, daemon.submit(c.family, c.y,
                                     deadline_ts=c.deadline_ts,
                                     request_id=c.request_id))
                   for c in claims]
        for c, t in tickets:
            try:
                res = t.wait(timeout=drain_timeout_s() + 60.0)
            except Exception as e:  # solver/deadline failure, not a crash
                _spool.fail(spool_dir, c, repr(e))
                continue
            _spool.complete(spool_dir, c, res["x"],
                            iiter=res["iiter"], status=res["status"])
            solved += 1
            _metrics.inc("serve.worker.solved")
    daemon.drain()
    _metrics.set_gauge("serve.worker.up", 0)
    _trace.event("serve.worker_exit", cat="serving", solved=solved)
    return solved


def serve_job(argv: Sequence[str], num_workers: int, spool_dir: str, *,
              max_relaunches: int = 2, **launch_kwargs):
    """Run a serve-forever worker fleet under the supervisor.

    ``argv`` is the worker command line (same placeholder contract as
    :func:`~pylops_mpi_tpu.resilience.supervisor.launch_job`); the
    worker is expected to call :func:`worker_main` on ``spool_dir``.
    The supervisor's ``on_relaunch`` hook sweeps the dead attempt's
    claimed requests back to pending before each relaunch, and a final
    sweep runs after the job ends (a terminal failure must still
    surface its orphans). Restart-rate lands on the
    ``supervisor.relaunches`` counter; the per-worker serving stats
    arrive in ``JobResult.metrics`` / ``job_report.json`` via the
    heartbeat-embedded registry as usual."""
    from ..resilience.supervisor import launch_job
    _spool.init_spool(spool_dir)

    def _recover(next_attempt: int, failure) -> None:
        requeued, quarantined = _spool.recover_claimed(spool_dir)
        _trace.event("serve.relaunch_recover", cat="serving",
                     attempt=next_attempt, requeued=requeued,
                     quarantined=quarantined,
                     failure_kind=getattr(failure, "kind", None))

    result = launch_job(argv, num_workers,
                        max_relaunches=max_relaunches,
                        on_relaunch=_recover, **launch_kwargs)
    _spool.recover_claimed(spool_dir)
    return result
