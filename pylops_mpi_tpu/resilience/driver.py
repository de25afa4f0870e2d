"""``resilient_solve`` — graceful degradation by precision escalation.

The narrow-storage fast path (ops/_precision.py, ISSUE 2) buys its
HBM-roofline wins with headroom: a bf16-stored operator can underflow a
denominator or overflow a recurrence scalar that the same system at f32
absorbs. The guarded fused solvers (ISSUE 6, solvers/basic.py) turn
that event into a ``BREAKDOWN`` status and a **last finite iterate**;
this driver turns it into a finished solve:

1. run the guarded fused solver at the current precision rung;
2. on ``breakdown``/``stagnation``, rebuild the operator ONE rung wider
   (``ops/_precision.escalate_dtype``: bf16 → f32 → f64, c64 → c128)
   and restart **from the last finite iterate** with the remaining
   iteration budget;
3. bounded by ``max_restarts`` (``PYLOPS_MPI_TPU_RESTARTS``, default
   2); every restart emits a structured ``solver.restart`` trace event.

The caller supplies an **operator factory** ``make_op(compute_dtype)``
(``compute_dtype=None`` on the first rung — the operator resolves the
env precision policy itself, exactly as a direct construction would),
because operators capture their storage dtype at construction; passing
a plain operator instead disables escalation (restarts are then only
possible for ``stagnation``, at the same precision, which is usually
futile — the driver stops instead).

Tuned plans survive restarts for free: the plan cache key
(tuning/plan.py) carries the dtype, so each rung replays its own plan
and invalidates nothing.
"""

from __future__ import annotations

import os
from collections import namedtuple
from typing import Callable, Optional, Sequence, Union

import numpy as np

from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace
from . import status as _rstatus

__all__ = ["resilient_solve", "refined_solve", "ResilientResult",
           "RefinedResult", "max_restarts_default"]

ResilientResult = namedtuple(
    "ResilientResult",
    ["x", "status", "iiter", "restarts", "compute_dtype", "cost",
     "attempts"])
ResilientResult.__doc__ = (
    "Outcome of a resilient solve: the final iterate, the final status "
    "NAME (``converged``/``maxiter``/``breakdown``/``stagnation``), "
    "total iterations across every attempt, the restart count, the "
    "compute dtype of the last attempt, its cost history, and a "
    "per-attempt record list (precision, iterations, status).")

_SOLVERS = ("cg", "cgls", "ista", "fista")


def max_restarts_default() -> int:
    """``PYLOPS_MPI_TPU_RESTARTS`` (default 2, floored at 0)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_RESTARTS", "2"))
    except ValueError:
        v = 2
    return max(0, v)


def _run_guarded(solver: str, Op, y, x, niter: int, tol: float,
                 damp: float, solver_kwargs: dict, M=None):
    from ..solvers.basic import cg_guarded, cgls_guarded
    from ..solvers.sparsity import ista_guarded, fista_guarded
    if solver == "cg":
        xn, it, cost, code = cg_guarded(Op, y, x, niter=niter, tol=tol,
                                        M=M)
    elif solver == "cgls":
        xn, it, cost, _, _, code = cgls_guarded(
            Op, y, x, niter=niter, damp=damp, tol=tol,
            normal=solver_kwargs.get("normal"), M=M)
    else:
        if M is not None:
            raise ValueError(
                f"M= (preconditioning) is not supported for {solver}")
        if x is None:
            from ..solvers.basic import _zero_like_model
            x = _zero_like_model(Op, y)
        fn = ista_guarded if solver == "ista" else fista_guarded
        kw = {k: v for k, v in solver_kwargs.items() if k != "normal"}
        xn, it, cost, code = fn(Op, y, x, niter=niter, tol=tol, **kw)
    return xn, it, cost, code


def resilient_solve(make_op: Union[Callable, object], y, x0=None, *,
                    solver: str = "cgls", niter: int = 100,
                    tol: float = 1e-4, damp: float = 0.0,
                    max_restarts: Optional[int] = None,
                    precisions: Optional[Sequence] = None,
                    M=None, refine: Optional[bool] = None,
                    **solver_kwargs) -> ResilientResult:
    """Solve with in-loop breakdown detection and bounded
    precision-escalation restarts (module docstring).

    ``make_op`` — operator factory ``make_op(compute_dtype)`` (or a
    plain operator, escalation disabled). ``precisions`` — explicit
    rung sequence of compute dtypes for attempts after the first
    (default: one :func:`~pylops_mpi_tpu.ops._precision.escalate_dtype`
    rung per restart). ``M`` — preconditioner threaded to the guarded
    CG/CGLS entries (ops/precond.py). ``refine`` — route the solve
    through :func:`refined_solve` (narrow inner solves + wide
    correction steps); default is the ``PYLOPS_MPI_TPU_REFINE`` knob.
    Extra ``solver_kwargs`` reach the guarded sparse solvers (``eps``,
    ``alpha``, ``threshkind``, ...) or CGLS (``normal``)."""
    from ..ops._precision import effective_compute_dtype, escalate_dtype
    from ..utils.deps import refine_enabled
    if solver not in _SOLVERS:
        raise ValueError(f"solver={solver!r}: expected one of {_SOLVERS}")
    if refine is None:
        refine = refine_enabled()
    if refine and callable(make_op) and solver in ("cg", "cgls"):
        rr = refined_solve(make_op, y, x0, solver=solver, niter=niter,
                           tol=tol, damp=damp, M=M, **solver_kwargs)
        status = {"converged": "converged", "maxpasses": "maxiter",
                  "stalled": "stagnation"}[rr.status]
        return ResilientResult(
            x=rr.x, status=status, iiter=rr.iiter,
            restarts=max(0, rr.passes - 1),
            compute_dtype=rr.attempts[-1]["compute_dtype"]
            if rr.attempts else "none",
            cost=rr.residuals, attempts=rr.attempts)
    if max_restarts is None:
        max_restarts = max_restarts_default()
    factory = make_op if callable(make_op) else None
    ladder = list(precisions) if precisions is not None else None

    x = x0
    cdt = None  # first rung: the operator's own (policy-resolved) dtype
    restarts = 0
    total_iiter = 0
    attempts = []
    cost = None
    while True:
        Op = factory(cdt) if factory is not None else make_op
        eff = effective_compute_dtype(Op)
        remaining = max(1, niter - total_iiter)
        x, it, cost, code = _run_guarded(solver, Op, y, x, remaining,
                                         tol, damp, solver_kwargs, M=M)
        total_iiter += it
        attempts.append({"compute_dtype": eff.name, "iiter": it,
                         "status": _rstatus.status_name(code)})
        if code in (_rstatus.CONVERGED, _rstatus.MAXITER):
            break
        # breakdown / stagnation: escalate one rung and restart from
        # the last finite iterate
        if ladder is not None:
            nxt = np.dtype(ladder.pop(0)) if ladder else None
        else:
            nxt = escalate_dtype(eff)
        if factory is None or nxt is None or restarts >= max_restarts:
            break
        restarts += 1
        _metrics.inc(f"solver.{solver}.restarts")
        _trace.event("solver.restart", cat="resilience", solver=solver,
                     status=_rstatus.status_name(code),
                     at_iter=total_iiter, restart=restarts,
                     from_dtype=eff.name, to_dtype=nxt.name)
        cdt = nxt
    return ResilientResult(x=x, status=_rstatus.status_name(code),
                           iiter=total_iiter, restarts=restarts,
                           compute_dtype=eff.name, cost=cost,
                           attempts=attempts)


# ------------------------------------------------------------ refinement
RefinedResult = namedtuple(
    "RefinedResult",
    ["x", "status", "iiter", "passes", "residuals", "narrow_frac",
     "attempts"])
RefinedResult.__doc__ = (
    "Outcome of an iteratively refined solve: the wide-precision "
    "iterate, status (``converged``/``maxpasses``/``stalled``), total "
    "inner iterations, correction-pass count, the per-pass wide "
    "residual norms, the fraction of operator applies executed at "
    "narrow precision, and a per-pass record list.")


class _NormalOperator:
    """``v ↦ OpᴴOp v + damp² v`` — the model-space normal system the
    damped-CGLS refinement pass solves for its correction. Lives
    outside the pytree registry on purpose: the refinement driver only
    runs it through the closure-capture solver path."""

    def __init__(self, Op, damp: float):
        n = int(Op.shape[1])
        self.shape = (n, n)
        self.dtype = Op.dtype
        self.mesh = getattr(Op, "mesh", None)
        self._Op = Op
        self._damp2 = float(damp) * float(damp)

    def matvec(self, x):
        v = self._Op.rmatvec(self._Op.matvec(x))
        return v + x * self._damp2 if self._damp2 else v

    rmatvec = matvec


def _wrap_wide(g, like):
    from ..distributedarray import DistributedArray
    return DistributedArray._wrap(like._from_global(g), like)


def refined_solve(make_op: Callable, y, x0=None, *, solver: str = "cg",
                  niter: int = 100, tol: float = 1e-10,
                  damp: float = 0.0, inner_dtype=None,
                  inner_niter: Optional[int] = None,
                  inner_tol: float = 1e-4, max_passes: int = 8,
                  M=None, wide_dtype=None,
                  **solver_kwargs) -> RefinedResult:
    """Mixed-precision iterative refinement: narrow inner (P)CG/CGLS
    solves, wide (f64) residuals and correction updates.

    Each pass recomputes the TRUE residual of the wide system —
    ``s = y − Ax`` (cg) or the gradient ``g = Aᴴ(y−Ax) − damp²x``
    (cgls) — at ``wide_dtype`` through ``make_op(wide_dtype)``, solves
    the correction system at the narrow rung through
    ``make_op(inner_dtype)`` (optionally preconditioned by ``M``), and
    applies ``x += d`` in wide precision. The narrow solver only ever
    sees the residual, whose solution is O(residual) small, so its
    limited range/precision bounds the CORRECTION error, not the
    solution error — bf16/f32 inner solves reach f64 accuracy while
    ≥80% of the matvec FLOPs run at the narrow dtype
    (``solver.refine.*`` telemetry counts them).

    Composition with escalation: an inner breakdown/stagnation, or a
    pass that fails to shrink the wide residual, escalates the inner
    rung one step (``escalate_dtype``) and re-runs the pass from the
    reverted iterate — the refinement analog of ``resilient_solve``'s
    restart. ``PYLOPS_MPI_TPU_REFINE=1`` routes ``resilient_solve``
    here for cg/cgls factories.

    ``inner_dtype=None`` lets the first narrow build resolve the env
    precision policy, exactly like ``resilient_solve``'s first rung.
    ``inner_tol`` is the per-pass relative tolerance of the correction
    solve (coarse on purpose — outer passes, not inner iterations, buy
    the final accuracy)."""
    import jax
    from ..ops._precision import effective_compute_dtype, escalate_dtype
    if solver not in ("cg", "cgls"):
        raise ValueError(f"solver={solver!r}: refinement supports "
                         "'cg' and 'cgls'")
    if not callable(make_op):
        raise TypeError(
            "refined_solve needs an operator FACTORY make_op("
            "compute_dtype) — it must build both the wide and the "
            "narrow operator; a plain operator cannot escalate")
    if wide_dtype is None:
        base = np.float64 if jax.config.jax_enable_x64 else np.float32
        wide_dtype = np.promote_types(base, np.dtype(y.dtype))
    wide_dtype = np.dtype(wide_dtype)
    if inner_niter is None:
        inner_niter = niter

    Opw = make_op(wide_dtype)
    cdt = inner_dtype
    Opn = make_op(np.dtype(cdt) if cdt is not None else None)
    per_apply = 2 if solver == "cgls" else 1

    yg = y._global().astype(wide_dtype)
    ynorm = float(np.linalg.norm(np.asarray(yg)))
    if solver == "cgls":
        gref = Opw.rmatvec(_wrap_wide(yg, y))._global()
        refnorm = float(np.linalg.norm(np.asarray(gref)))
    else:
        refnorm = ynorm
    refnorm = refnorm if refnorm > 0 else 1.0

    if x0 is not None:
        x = _wrap_wide(x0._global().astype(wide_dtype), x0)
    else:
        from ..solvers.basic import _zero_like_model
        x = _zero_like_model(Opw, _wrap_wide(yg, y))

    residuals = []
    attempts = []
    total_iiter = 0
    n_narrow = 0.0
    n_wide = 0.0
    status = "maxpasses"
    prev_norm = np.inf
    passes = 0
    while passes < max_passes:
        # ---- wide TRUE residual -----------------------------------
        ax = Opw.matvec(x)._global().astype(wide_dtype)
        s_g = yg - ax
        n_wide += 1
        if solver == "cgls":
            g = Opw.rmatvec(_wrap_wide(s_g, y))._global() \
                .astype(wide_dtype)
            n_wide += 1
            if self_damp := float(damp):
                g = g - x._global() * (self_damp * self_damp)
            rnorm = float(np.linalg.norm(np.asarray(g)))
        else:
            rnorm = float(np.linalg.norm(np.asarray(s_g)))
        residuals.append(rnorm)
        if rnorm <= tol * refnorm:
            status = "converged"
            break
        if passes > 0 and rnorm >= prev_norm:
            # the last correction did not help: revert, escalate the
            # inner rung, retry — the refinement analog of a restart
            nxt = escalate_dtype(effective_compute_dtype(Opn))
            if nxt is None:
                status = "stalled"
                break
            x = x_prev  # noqa: F821 — rnorm >= prev_norm implies set
            _trace.event("solver.refine_escalate", cat="resilience",
                         solver=solver, at_pass=passes,
                         to_dtype=nxt.name)
            _metrics.inc("solver.refine.escalations")
            Opn = make_op(nxt)
            prev_norm = np.inf
            continue

        # ---- narrow correction solve ------------------------------
        # the fused solvers' stop test is ABSOLUTE (max(kold) > tol,
        # kold = r·z ≈ ||r||²); refinement needs the inner tolerance
        # RELATIVE to the pass's own rhs — each pass then contracts
        # the wide residual by ≈ inner_tol instead of stalling at it
        passes += 1
        itol = float((inner_tol * rnorm) ** 2)
        eff = effective_compute_dtype(Opn)
        ndt = np.dtype(Opn.dtype)
        if solver == "cgls" and float(damp):
            Nop = _NormalOperator(Opn, damp)
            rhs = _wrap_wide(g.astype(ndt), x)
            d, it, _, code = _run_guarded(
                "cg", Nop, rhs, None, inner_niter, itol, 0.0,
                {}, M=M)
            napp = 2.0 * (it + 1)      # each normal apply = 2 of A
        else:
            rhs = _wrap_wide(s_g.astype(ndt), y)
            d, it, _, code = _run_guarded(
                solver, Opn, rhs, None, inner_niter, itol, 0.0,
                solver_kwargs, M=M)
            napp = float(per_apply) * (it + 1)
        total_iiter += it
        n_narrow += napp
        attempts.append({"compute_dtype": eff.name, "iiter": it,
                         "status": _rstatus.status_name(code),
                         "residual": rnorm})
        _metrics.inc("solver.refine.passes")

        # ---- wide correction update -------------------------------
        x_prev = x
        prev_norm = rnorm
        x = _wrap_wide(
            x._global() + d._global().astype(wide_dtype), x)
        if code not in (_rstatus.CONVERGED, _rstatus.MAXITER):
            nxt = escalate_dtype(eff)
            if nxt is not None:
                _trace.event("solver.refine_escalate",
                             cat="resilience", solver=solver,
                             at_pass=passes, to_dtype=nxt.name)
                _metrics.inc("solver.refine.escalations")
                Opn = make_op(nxt)

    _metrics.inc("solver.refine.narrow_matvecs", n_narrow)
    _metrics.inc("solver.refine.wide_matvecs", n_wide)
    frac = n_narrow / max(1.0, n_narrow + n_wide)
    return RefinedResult(x=x, status=status, iiter=total_iiter,
                         passes=passes, residuals=residuals,
                         narrow_frac=frac, attempts=attempts)
