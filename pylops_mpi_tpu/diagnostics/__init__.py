"""Runtime observability subsystem (round 9).

The reference ships no runtime introspection at all; after three perf
rounds this repo had many tuned kernels and zero visibility into where
time, bytes and iterations actually go. Four modules make the folklore first-class:

- :mod:`~pylops_mpi_tpu.diagnostics.trace` — structured span tracer
  (context-manager API, nested spans, thread-safe ring buffer) emitting
  Chrome-trace-event JSONL, gated by ``PYLOPS_MPI_TPU_TRACE``; wired
  through every operator ``matvec``/``rmatvec``, the hand-scheduled
  collectives, and the solver entry points. The same spans always
  land on the profiler's clock (``pmt.*`` named scopes under a jit
  trace, ``TraceAnnotation`` outside it).
- :mod:`~pylops_mpi_tpu.diagnostics.costmodel` — per-op cost registry
  (FLOPs, HBM bytes, ICI bytes per apply) generalizing the comm-volume
  model previously private to ``ops/matrixmult.py``'s auto-select,
  plus the per-chip peak tables and a roofline predictor.
- :mod:`~pylops_mpi_tpu.diagnostics.telemetry` — per-iteration
  convergence telemetry captured from INSIDE the fused solver
  ``while_loop``\\ s via ``jax.debug.callback``; off by default, with
  an HLO pin (``utils/hlo.py::assert_no_host_callbacks``) proving the
  donated/fused hot path carries zero host callbacks when disabled.
- :mod:`~pylops_mpi_tpu.diagnostics.profiler` — the deadline-aware
  stage runner and the central per-stage wall-budget table (tuner
  searches, watched multi-host phases, serving batches).

Fleet observability (ISSUE 10) adds the cross-process half:

- :mod:`~pylops_mpi_tpu.diagnostics.metrics` — process-wide
  counters/gauges/histograms (solver iterations, guard verdicts,
  collective bytes, plan-cache hits, retries, per-stage wall) gated by
  ``PYLOPS_MPI_TPU_METRICS``, with atomic periodic snapshots and the
  snapshot embedded in every supervised heartbeat.
- :mod:`~pylops_mpi_tpu.diagnostics.aggregate` — merges per-worker
  trace JSONLs into ONE clock-aligned Chrome trace (``pid=rank``),
  stamping every matched collective with ``skew_us`` +
  ``straggler_rank`` and computing per-solve critical paths.
- ``python -m pylops_mpi_tpu.diagnostics`` — the jax-free CLI over
  both (:mod:`~pylops_mpi_tpu.diagnostics.__main__`).

See ``docs/observability.md`` for the env knobs and artifact schema.
"""

from . import trace
from . import costmodel
from . import telemetry
from . import profiler
from . import metrics
from . import aggregate

from .trace import (trace_mode, trace_enabled, span, event, counter,
                    get_events, clear_events, dump, span_tree)
from .costmodel import (OpCost, estimate, register_cost, roofline,
                        summa_comm_volume, pencil_transpose_cost,
                        peak_flops, peak_hbm_gbps, peak_ici_gbps,
                        device_peaks)
from .telemetry import (telemetry_enabled, iteration, history,
                        clear_history, telemetry_signature)
from .profiler import STAGE_BUDGETS, stage_budget, DeadlineRunner
from .metrics import (metrics_mode, metrics_enabled, inc, set_gauge,
                      observe, timer, snapshot, clear_metrics,
                      write_snapshot, read_snapshot)
from .aggregate import (load_events, merge_traces, aggregate_files,
                        critical_path)

__all__ = [
    "trace", "costmodel", "telemetry", "profiler", "metrics",
    "aggregate",
    "metrics_mode", "metrics_enabled", "inc", "set_gauge", "observe",
    "timer", "snapshot", "clear_metrics", "write_snapshot",
    "read_snapshot",
    "load_events", "merge_traces", "aggregate_files", "critical_path",
    "trace_mode", "trace_enabled", "span", "event", "counter",
    "get_events", "clear_events", "dump", "span_tree",
    "OpCost", "estimate", "register_cost", "roofline",
    "summa_comm_volume", "pencil_transpose_cost", "peak_flops",
    "peak_hbm_gbps", "peak_ici_gbps", "device_peaks",
    "telemetry_enabled", "iteration", "history", "clear_history",
    "telemetry_signature",
    "STAGE_BUDGETS", "stage_budget", "DeadlineRunner",
]
