"""Always-on solve service (ISSUE 12): continuous batching over the
block engine.

The serving subsystem turns the library's one-shot solvers into a
long-lived daemon for single-RHS traffic:

- :mod:`.engine` — :class:`WarmPool`: compiled block-CG/CGLS programs
  per (operator family, K bucket), pre-warmed at startup so
  first-request latency is compile-free; ragged fills are
  zero-padded to the bucket (exact, by per-column freeze).
- :mod:`.queue` — :class:`AdmissionQueue` (bounded, rejecting —
  backpressure) + :class:`Dispatcher` (continuous batcher: full
  bucket / window expiry / deadline-forced undersized dispatch, every
  batch under a ``DeadlineRunner``).
- :mod:`.spool` — durable filesystem queue for supervised workers
  (atomic claim/complete/recover; crash-safe at any instant).
- :mod:`.service` — :class:`SolveDaemon` (in-process facade),
  :func:`worker_main` (supervised replica with SIGTERM drain), and
  :func:`serve_job` (serve-forever under the PR 7 supervisor with
  crashed-attempt request recovery).

See ``docs/serving.md`` for architecture, knobs, and deadline /
backpressure semantics.
"""

from . import engine, queue, service, spool
from .engine import FamilySpec, WarmPool, BlockOutcome, k_buckets, \
    bucket_for
from .queue import (AdmissionQueue, Dispatcher, QueueFull, Ticket,
                    pack, queue_bound, batch_window_s)
from .service import SolveDaemon, worker_main, serve_job, \
    drain_timeout_s

__all__ = ["engine", "queue", "service", "spool",
           "FamilySpec", "WarmPool", "BlockOutcome", "k_buckets",
           "bucket_for",
           "AdmissionQueue", "Dispatcher", "QueueFull", "Ticket",
           "pack", "queue_bound", "batch_window_s",
           "SolveDaemon", "worker_main", "serve_job",
           "drain_timeout_s"]
