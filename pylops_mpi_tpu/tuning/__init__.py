"""Autotuning: measured plan selection for the distributed kernels.

The operator stack exposes a large discrete plan space — SUMMA
``gather`` vs ``stat_a``, ``overlap=on|off``, ``comm_chunks=K``,
Pallas-vs-XLA normal path — previously hand-set via env knobs or
picked by the analytic cost model alone. This package closes the
predict→measure loop (the XLA GEMM-autotuner pattern; arXiv
2112.09017 / 2112.01075 both show the best schedule must be searched,
not assumed):

- :mod:`.space` — declared per-op tuning spaces + cost-model seeds;
- :mod:`.search` — budget-bounded measurement of the top candidates;
- :mod:`.cache` — the persistent, schema-versioned JSON plan cache
  (``PYLOPS_MPI_TPU_TUNE_CACHE``);
- :mod:`.plan` — ``get_plan()``, the seam operators consult at
  construction when ``PYLOPS_MPI_TPU_TUNE=on|auto`` (default ``off``
  — bit-identical HLO to an untuned build; explicit kwargs always
  override the tuner).

``python -m pylops_mpi_tpu.tuning`` sweeps the flagship shapes
offline and banks a cache artifact. See ``docs/tuning.md``.
"""

from .plan import (Plan, get_plan, tune_mode, tune_enabled, plan_key,
                   shape_bucket, applied_provenance)
from .space import (Axis, TuningSpace, space_for, register_space,
                    candidates, rank, default_params)
from .search import measure_candidates
from . import cache

__all__ = ["Plan", "get_plan", "tune_mode", "tune_enabled", "plan_key",
           "shape_bucket", "applied_provenance",
           "Axis", "TuningSpace", "space_for", "register_space",
           "candidates", "rank", "default_params",
           "measure_candidates", "cache"]
