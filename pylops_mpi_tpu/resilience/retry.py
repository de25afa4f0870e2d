"""Bounded retry with exponential backoff.

Multi-host bring-up is the flakiest moment of a pod job: the
``jax.distributed`` coordinator may not be listening yet, a DNS entry
may lag the pod scheduler, a preempted peer may rejoin seconds late.
The reference stack leans on ``mpiexec`` to re-run the world; here one
controller process must absorb transient faults itself. This module is
the ONE retry/backoff implementation, used by
:func:`pylops_mpi_tpu.parallel.mesh.initialize_multihost` — where the
failure is transient-by-construction and a bounded retry is the
difference between a lost job and a running one.

Retries are **bounded** (``PYLOPS_MPI_TPU_RETRIES``, default 3 extra
attempts) with doubling backoff from
``PYLOPS_MPI_TPU_RETRY_BACKOFF`` seconds (default 0.5, capped at 30 s
per sleep); every retry emits a structured ``resilience.retry`` trace
event so a flaky-but-recovering init is visible in the JSONL artifact
instead of silently eating minutes. The final failure re-raises the
last exception unchanged — retry must never LAUNDER an error.

**Jitter** (``PYLOPS_MPI_TPU_RETRY_JITTER``, default 0 — exact
doubling stays the pinned behavior): after a supervisor relaunch, P
workers all lose the coordinator at the same instant and would
otherwise reconnect in lockstep, hammering the restarted coordinator
at exactly t+0.5, t+1.5, t+3.5, … The decorrelating jitter shrinks
each sleep by a uniform random fraction up to the knob (AWS
"full/decorrelated jitter" family: ``wait × (1 − U[0,1)·j)``), so the
stampede spreads while the CAP and the bounded attempt count are
unchanged. The supervisor sets ``j=0.25`` in its worker env.

**Retryability** (``retry_if``): a coarse exception tuple cannot say
"retry 'connection refused' but not 'address already in use'"; the
optional predicate sees the caught exception and vetoes the retry
(re-raising unchanged) when it returns False.
"""

from __future__ import annotations

import os
import random
import time
from typing import Callable, Optional, Tuple, Type

from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace

__all__ = ["retry_call", "default_retries", "default_backoff_s",
           "default_jitter"]

_MAX_SLEEP_S = 30.0


def default_retries() -> int:
    """``PYLOPS_MPI_TPU_RETRIES`` (default 3, floored at 0 — 0 means
    one attempt, no retries)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_RETRIES", "3"))
    except ValueError:
        v = 3
    return max(0, v)


def default_backoff_s() -> float:
    """``PYLOPS_MPI_TPU_RETRY_BACKOFF`` initial sleep in seconds
    (default 0.5, floored at 0 for tests that must not sleep)."""
    try:
        v = float(os.environ.get("PYLOPS_MPI_TPU_RETRY_BACKOFF", "0.5"))
    except ValueError:
        v = 0.5
    return max(0.0, v)


def default_jitter() -> float:
    """``PYLOPS_MPI_TPU_RETRY_JITTER`` decorrelation fraction in
    [0, 1] (default 0.0 — deterministic doubling; the supervisor sets
    0.25 for its workers). Clamped: 1.0 means a sleep may shrink to
    ~0, never grow past the doubling schedule's cap."""
    try:
        v = float(os.environ.get("PYLOPS_MPI_TPU_RETRY_JITTER", "0"))
    except ValueError:
        v = 0.0
    return min(1.0, max(0.0, v))


def retry_call(fn: Callable, *args,
               retries: Optional[int] = None,
               backoff_s: Optional[float] = None,
               exceptions: Tuple[Type[BaseException], ...] = (Exception,),
               retry_if: Optional[Callable[[BaseException], bool]] = None,
               jitter: Optional[float] = None,
               describe: str = "call",
               sleep: Callable[[float], None] = time.sleep,
               rng: Optional[random.Random] = None,
               **kwargs):
    """Call ``fn(*args, **kwargs)``; on an exception from
    ``exceptions`` that ``retry_if`` (when given) deems retryable,
    sleep (doubling backoff, capped, optionally jittered — module
    docstring) and retry up to ``retries`` more times. Emits one
    ``resilience.retry`` trace event per retry; the last failure — and
    any non-retryable one — propagates unchanged.

    ``sleep`` and ``rng`` are injectable so the chaos tests neither
    wait out real backoffs nor depend on global random state."""
    retries = default_retries() if retries is None else max(0, retries)
    backoff = default_backoff_s() if backoff_s is None else max(0.0,
                                                                backoff_s)
    jitter = default_jitter() if jitter is None \
        else min(1.0, max(0.0, jitter))
    attempt = 0
    while True:
        try:
            return fn(*args, **kwargs)
        except exceptions as e:
            if retry_if is not None and not retry_if(e):
                raise
            attempt += 1
            if attempt > retries:
                raise
            wait = min(backoff * (2 ** (attempt - 1)), _MAX_SLEEP_S)
            if jitter > 0.0 and wait > 0.0:
                u = (rng or random).random()
                wait *= 1.0 - jitter * u
            _metrics.inc("resilience.retries")
            _trace.event("resilience.retry", cat="resilience",
                         what=describe, attempt=attempt,
                         retries=retries, backoff_s=round(wait, 3),
                         error=repr(e)[:200])
            if wait > 0:
                sleep(wait)
