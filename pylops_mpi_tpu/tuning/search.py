"""Measured plan search: cost-model-seeded, budget-bounded timing.

The refinement half of the autotuner: rank the declared candidates
with the analytic seed (``space.rank`` → ``diagnostics/costmodel``),
then TIME the top-k with the package's benchmark timers
(``utils/benchmark.time_callable`` — same sync discipline as the
``@benchmark`` decorator) inside a :class:`DeadlineRunner` budget
(``STAGE_BUDGETS["tune"]``), so a search is bounded. Every trial is emitted as a structured ``tuning.trial`` trace
event — the replay proof ("zero timing trials on the second run")
counts exactly these events.

Selection is conservative: the winner must beat the DEFAULT
configuration by a margin (``PYLOPS_MPI_TPU_TUNE_MARGIN``, default
2%) or the default is kept — a noisy micro-benchmark must not flip a
schedule for a within-noise difference (the acceptance bar: a tuned
plan is never meaningfully slower than today's defaults).
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..diagnostics import trace as _trace
from ..diagnostics.profiler import DeadlineRunner, stage_budget
from . import space as _space

__all__ = ["measure_candidates", "tune_budget_s", "tune_topk",
           "tune_margin"]


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ[name])
    except (KeyError, ValueError):
        return default


def tune_budget_s() -> int:
    """Wall budget for ONE search (seconds):
    ``PYLOPS_MPI_TPU_TUNE_BUDGET`` when set, else the central
    ``STAGE_BUDGETS["tune"]`` entry."""
    b = _env_int("PYLOPS_MPI_TPU_TUNE_BUDGET", 0)
    if b > 0:
        return b
    return stage_budget("tune")


def tune_topk() -> int:
    """How many seed-ranked candidates get timed (default 4; the
    default configuration is always included regardless)."""
    return max(1, _env_int("PYLOPS_MPI_TPU_TUNE_TOPK", 4))


def tune_margin() -> float:
    """Fractional win required to move off the default (default 2%)."""
    return max(0.0, _env_float("PYLOPS_MPI_TPU_TUNE_MARGIN", 0.02))


def _trial_list(space: _space.TuningSpace, ctx: Dict) -> List[Dict]:
    """Measurement set: the default configuration first (the race
    baseline that must always be in the set), then the seed ranking,
    deduplicated, capped at top-k."""
    ranked = _space.rank(space, ctx)
    dflt = _space.default_params(space, ctx)
    ordered = [dflt] + [p for p in ranked if p != dflt]
    return ordered[:max(2, tune_topk())] if len(ordered) > 1 else ordered


def measure_candidates(space: _space.TuningSpace, ctx: Dict,
                       factory: Callable[[Dict], Callable],
                       budget_s: Optional[int] = None,
                       repeats: int = 3,
                       runner: Optional[DeadlineRunner] = None) \
        -> Tuple[Optional[Dict], List[Dict]]:
    """Time the top candidates and pick the winner.

    ``factory(params)`` builds one candidate configuration (an
    operator constructed with EXPLICIT kwargs — explicit kwargs never
    re-enter the tuner) and returns a zero-arg apply; the first call
    pays compile, then ``repeats`` timed calls follow
    (``utils/benchmark.time_callable``). Trials run through a
    :class:`DeadlineRunner` (budget from :func:`tune_budget_s` unless
    given): once the budget is exhausted the remaining candidates are
    SKIPPED (recorded), and whatever was measured decides.

    Returns ``(winner_params, trials)``; ``winner_params`` is ``None``
    when nothing could be measured (caller falls back to the seed).
    The default configuration wins ties and near-ties
    (:func:`tune_margin`).
    """
    from ..utils.benchmark import time_callable
    cands = _trial_list(space, ctx)
    dflt = _space.default_params(space, ctx)
    if budget_s is None:
        budget_s = tune_budget_s()
    if runner is None:
        runner = DeadlineRunner(deadline_ts=time.time() + budget_s,
                                min_stage_s=1)
    trials: List[Dict] = []
    measured: List[Tuple[float, Dict]] = []
    for i, params in enumerate(cands):
        def _one(eff_timeout, params=params):
            apply_fn = factory(params)
            stats = time_callable(apply_fn, repeats=repeats, warmup=1)
            return {"params": params, **stats}, None

        rec = runner.run(f"tune.{space.op}.{i}", _one, budget_s)
        trial = {"op": space.op, "params": params,
                 "skipped": bool(rec.get("skipped")),
                 "ok": bool(rec.get("ok")),
                 "seconds": rec.get("seconds")}
        if rec.get("error"):
            trial["error"] = rec["error"]
        if rec.get("ok") and isinstance(rec.result, dict):
            trial["best_s"] = rec.result.get("best_s")
            trial["mean_s"] = rec.result.get("mean_s")
            # compile-vs-run split (AOT tier): how much of the trial's
            # budget went to the warmup compile rather than the timed
            # measurements. With a banked executable or a warm
            # persistent compilation cache this collapses toward
            # best_s — budget buys measurements, not compiles.
            trial["compile_s"] = rec.result.get("compile_s")
            measured.append((float(rec.result["best_s"]), params))
        trials.append(trial)
        # the replay-proof event: a warm cache produces ZERO of these
        _trace.event("tuning.trial", cat="tuning", op=space.op,
                     params=params, skipped=trial["skipped"],
                     ok=trial["ok"], best_s=trial.get("best_s"),
                     compile_s=trial.get("compile_s"))
    if not measured:
        return None, trials
    best_t, best_p = min(measured, key=lambda t: t[0])
    t_default = next((t for t, p in measured if p == dflt), None)
    if (best_p != dflt and t_default is not None
            and best_t > t_default * (1.0 - tune_margin())):
        # within noise of the default: keep the default (hysteresis)
        best_t, best_p = t_default, dflt
    _trace.event("tuning.winner", cat="tuning", op=space.op,
                 params=best_p, best_s=best_t,
                 default_s=t_default,
                 n_measured=len(measured))
    return dict(best_p), trials
