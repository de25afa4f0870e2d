"""The deadline-aware stage runner.

:class:`DeadlineRunner` + :data:`STAGE_BUDGETS` — the central
per-stage wall-budget table (tuner searches, watched multi-host
phases, serving batches) and a runner that (a) caps each stage's
timeout at ``min(budget, window remaining)``, (b)
records whether a killed stage still BANKED a partial artifact, and
(c) SKIPS stages the remaining window cannot fit.

(The device-level view is ``jax.profiler.trace(dir)`` around any
region: the program's spans land in it by the rule of
``diagnostics/trace.py``.)

STANDALONE-LOADABLE BY DESIGN: module-level imports are stdlib only
and there are no relative imports, so a jax-free supervisor process
can load this file directly via
``importlib.util.spec_from_file_location`` without pulling the package
(and jax) in. Trace emission is lazy and guarded for the same reason.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional

__all__ = ["STAGE_BUDGETS", "stage_budget", "DeadlineRunner",
           "StageRecord"]


# ------------------------------------------------------------ budget table
# Per-stage wall budgets, seconds. Env override name:
# PROBE_<STAGE>_TIMEOUT.
STAGE_BUDGETS: Dict[str, int] = {
    # the autotuner sweep (python -m pylops_mpi_tpu.tuning); also the
    # per-search budget tuning.search enforces in-process
    # (PYLOPS_MPI_TPU_TUNE_BUDGET overrides for a single search)
    "tune":           600,
    # elastic-runtime watched phases (resilience/elastic.py
    # watched_call deadlines; PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT
    # overrides globally, PROBE_<STAGE>_TIMEOUT per stage):
    # blocking jax.distributed bring-up, blocking multi-host
    # checkpoint save/load, and the CI chaos leg's whole
    # kill/recover suite
    "multihost_init":  300,
    "checkpoint_io":   600,
    "multihost_chaos": 900,
    # serving-daemon stages (serving/queue.py dispatcher wraps every
    # packed batch solve in a DeadlineRunner with this budget; the CI
    # serve-forever smoke uses serve_smoke as its job timeout)
    "serve_batch":     120,
    "serve_smoke":     900,
}


def stage_budget(stage: str, env: Optional[Dict] = None) -> int:
    """Wall budget (seconds) for one stage: the env override
    (``PROBE_<STAGE>_TIMEOUT``) when set and parseable, else the table
    entry. Unknown stages raise — a typo'd stage name must not
    silently get some default."""
    if stage not in STAGE_BUDGETS:
        raise KeyError(f"unknown stage {stage!r}; known: "
                       f"{sorted(STAGE_BUDGETS)}")
    env = os.environ if env is None else env
    raw = env.get("PROBE_" + stage.upper() + "_TIMEOUT")
    if raw is not None:
        try:
            return int(raw)
        except ValueError:
            pass  # malformed override: fall through to the table
    return STAGE_BUDGETS[stage]


# --------------------------------------------------------- deadline runner
class StageRecord(dict):
    """One stage outcome (a plain dict for easy JSON banking):
    ``stage``, ``budget_s``, ``effective_timeout_s``, ``seconds``,
    ``ok``, ``skipped``, ``banked_partial``, ``hit_budget``,
    ``error``. ``result`` holds the stage's parsed artifact (may be a
    salvaged partial)."""

    @property
    def result(self):
        return self.get("result")


class DeadlineRunner:
    """Run stages against a hard window deadline.

    ``fn`` passed to :meth:`run` receives the EFFECTIVE timeout
    (seconds) and returns ``(result, err)`` — exactly one of the two
    is None; ``result`` may be a salvaged partial when the stage was
    cut at the timeout (detected here via its
    ``salvaged_after_timeout`` stamp). The runner:

    - caps each stage at ``min(budget, remaining window)`` (a stage
      never eats past the deadline);
    - skips a stage outright when the remaining window is under
      ``min_stage_s`` (better to yield the window for the next probe
      than to start a stage that cannot finish);
    - records every outcome (:attr:`records`) — including whether a
      killed stage still banked a partial artifact — and emits a
      structured trace event per stage when the trace layer is
      available and enabled.
    """

    def __init__(self, deadline_ts: Optional[float] = None,
                 min_stage_s: int = 30,
                 log: Optional[Callable[[Dict], None]] = None):
        self.deadline_ts = deadline_ts
        self.min_stage_s = int(min_stage_s)
        self._log = log
        self.records: List[StageRecord] = []

    def remaining(self) -> Optional[float]:
        """Seconds left in the window (None = no deadline)."""
        if self.deadline_ts is None:
            return None
        return self.deadline_ts - time.time()

    def _emit(self, rec: StageRecord) -> None:
        self.records.append(rec)
        payload = {k: v for k, v in rec.items() if k != "result"}
        if self._log is not None:
            try:
                self._log(dict(payload))
            except Exception:
                pass
        try:
            # only if the trace layer is ALREADY imported: this module
            # is file-path-loaded by jax-free supervisors, and emitting
            # here must never pull the package (and jax) into them
            import sys
            tr = sys.modules.get("pylops_mpi_tpu.diagnostics.trace")
            if tr is not None:
                tr.event(f"harvest.{rec['stage']}", cat="harvest",
                         **payload)
        except Exception:
            pass

    def run(self, stage: str, fn: Callable, budget_s: int) -> StageRecord:
        rem = self.remaining()
        if rem is not None and rem < min(budget_s, self.min_stage_s):
            rec = StageRecord(stage=stage, budget_s=budget_s,
                              skipped=True, ok=False,
                              reason="window exhausted "
                                     f"({rem:.0f}s remaining)",
                              result=None)
            self._emit(rec)
            return rec
        eff = int(budget_s) if rem is None \
            else max(1, min(int(budget_s), int(rem)))
        t0 = time.time()
        try:
            result, err = fn(eff)
        except Exception as e:  # a crashing stage must not end the window
            result, err = None, f"stage raised: {e!r}"
        seconds = round(time.time() - t0, 1)
        banked_partial = bool(
            isinstance(result, dict)
            and (result.get("salvaged_after_timeout")
                 or result.get("partial")))
        rec = StageRecord(
            stage=stage, budget_s=int(budget_s),
            effective_timeout_s=eff, seconds=seconds,
            ok=result is not None and not err,
            skipped=False,
            hit_budget=seconds >= eff - 1,
            banked_partial=banked_partial,
            result=result)
        if err:
            rec["error"] = str(err)[:300]
        self._emit(rec)
        return rec

    def report(self) -> Dict:
        """Summary for artifacts: per-stage outcomes (without the
        payloads) + whether the window was yielded with stages
        unrun."""
        return {
            "stages": [{k: v for k, v in r.items() if k != "result"}
                       for r in self.records],
            "skipped": [r["stage"] for r in self.records
                        if r.get("skipped")],
            "banked_partials": [r["stage"] for r in self.records
                                if r.get("banked_partial")],
            "remaining_s": (None if self.deadline_ts is None
                            else round(self.remaining(), 1)),
        }


# convenience for scripts that bank runner reports next to artifacts
def dump_report(runner: DeadlineRunner, path: str) -> None:
    with open(path, "w") as f:
        json.dump(runner.report(), f, indent=1)
