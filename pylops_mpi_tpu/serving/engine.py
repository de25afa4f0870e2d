"""Warm-executable pool: compiled block programs, ready before traffic.

The serving daemon's latency floor is compile time — a cold
(operator family, K) pair pays seconds of XLA compilation on the
request that first needs it. The :class:`WarmPool` removes that cliff:

- **Families** — a :class:`FamilySpec` names one operator instance plus
  its solver configuration (``cg``/``cgls``, ``niter``, ``tol``,
  ``damp``). The SAME instance is used for every solve and every
  prewarm, so the fused-executable cache in ``solvers/basic.py``
  (keyed on ``id(Op)``) hits by construction.
- **K buckets** — incoming fills are rounded up to the next width in
  ``PYLOPS_MPI_TPU_SERVE_K_BUCKETS`` (default ``1,2,4,8,16``) and the
  short side padded with zero columns. Padding is EXACT: block-Krylov
  recurrences are column-independent (every scalar is a per-column
  ``col_dot``), a zero column's residual is zero so it freezes at
  iteration 0, and the padded program is the same compiled executable
  the full bucket uses — so K distinct fills share one program instead
  of K programs.
- **Prewarm** — at startup the pool compiles the widths it is given,
  else every configured bucket, by running a zero-RHS solve per
  (family, K): zero data means zero
  initial residual, the fused ``while_loop`` condition is false at
  entry, and the call compiles the program without executing a single
  iteration.

Per-column robustness (one tenant must not hurt its batch-mates) is
inherited from the block solvers: each column freezes on its OWN
convergence test, and with ``PYLOPS_MPI_TPU_GUARDS=on`` a breakdown
column is frozen with a per-column verdict while the rest run to their
own finish. Serve deployments should run with guards on — without
them a non-finite column collapses the shared loop condition for the
whole batch (see ``docs/serving.md#poisoned-columns``).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..distributedarray import DistributedArray
from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace

__all__ = ["k_buckets", "bucket_for", "FamilySpec", "BlockOutcome",
           "WarmPool"]

_DEFAULT_BUCKETS = (1, 2, 4, 8, 16)

# (family signature, bucket) pairs whose program went through a
# compile in THIS process — shared across WarmPool instances so a
# daemon restart (fresh pool, fresh operator instance, identical
# program) does not silently recompile at prewarm (the id(Op)-keyed
# fused cache cannot see the equivalence; the signature can).
_WARMED_SIGS: set = set()


def clear_warmed_signatures() -> None:
    """Drop the process-wide prewarm ledger (test isolation)."""
    _WARMED_SIGS.clear()


def k_buckets() -> Tuple[int, ...]:
    """``PYLOPS_MPI_TPU_SERVE_K_BUCKETS`` parsed to a sorted tuple of
    distinct positive widths (default ``(1, 2, 4, 8, 16)``; malformed
    tokens are dropped, an empty survivor set falls back to the
    default — a typo must not leave the pool bucketless)."""
    raw = os.environ.get("PYLOPS_MPI_TPU_SERVE_K_BUCKETS", "")
    vals = set()
    for tok in raw.split(","):
        tok = tok.strip()
        if tok.isdigit() and int(tok) >= 1:
            vals.add(int(tok))
    return tuple(sorted(vals)) if vals else _DEFAULT_BUCKETS


def bucket_for(count: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest configured bucket that fits ``count`` columns (the
    largest bucket when ``count`` overflows them all — the caller is
    expected to chunk at the max bucket, which the dispatcher does by
    construction)."""
    bs = tuple(buckets) if buckets else k_buckets()
    for b in bs:
        if b >= count:
            return b
    return bs[-1]


@dataclass(frozen=True)
class FamilySpec:
    """One servable operator family: the operator INSTANCE (reused for
    every solve so the fused cache hits), the engine and its fixed
    solve parameters. ``tol=0.0`` pins every column to the full
    ``niter`` schedule, so a packed solve runs the iterations its
    single-RHS oracle runs, and zero padding is exact. How close the
    answers then are depends on the program the bucket compiles. A
    ``K=1`` bucket IS the single-RHS program (same cache entry, same
    sweep schedule): bit for bit on every backend. A wider bucket on
    the classic two-sweep schedule — every backend's ``cg``; ``cgls``
    on the CPU and wherever ``solvers/basic._resolve_normal`` answers
    no — differs only where the backend's K-column product rounds
    unlike its one-column product (the CPU's slow race in
    ``tests/test_serving.py`` reads 1.5e-6 absolute). A wider ``cgls``
    bucket on the one-sweep schedule (a batched real ``MPIBlockDiag``
    on a TPU, at the widths the chip has shown to pay) carries the
    gradient recurrence instead: packed and single-RHS answers agree
    within the schedule's drift band (1.1 x the classic error to the
    solution, ``tests/test_cgls_normal_default.py``)."""
    name: str
    operator: object
    solver: str = "cgls"          # "cg" | "cgls"
    niter: int = 10
    tol: float = 0.0
    damp: float = 0.0
    dtype: object = np.float32
    # optional preconditioner (ops/precond.py) threaded into the block
    # solvers; part of the family identity — the fused cache keys on
    # id(M), so every bucket of the family reuses one compiled PCG/
    # PCGLS program, and M=None families lower bit-identically to the
    # pre-preconditioner engine
    M: object = None
    # opt-in marker for families served with PYLOPS_MPI_TPU_AUTODIFF=on
    # whose callers differentiate through the solve (autodiff/implicit).
    # Folded into signature() ONLY when True so the default False keeps
    # every existing family signature — and therefore every prewarm/AOT
    # bank key — byte-identical to the pre-autodiff engine.
    differentiable: bool = False

    def __post_init__(self):
        if self.solver not in ("cg", "cgls"):
            raise ValueError(
                f"solver={self.solver!r}: expected 'cg' or 'cgls'")

    @property
    def nrows(self) -> int:
        return int(self.operator.shape[0])

    def signature(self) -> Tuple:
        """Structural identity of the family's compiled program:
        solver configuration plus the operator's AOT fingerprint
        (class, shape, dtype, leaf avals — ``aot.op_signature``).
        Two specs with equal signatures lower to the SAME program even
        when their operator INSTANCES differ (a daemon restart builds
        a fresh operator), which is what lets prewarm skip recompiles
        it used to pay silently. Preconditioned families fold in
        ``id(M)`` — M is closure-captured, so only the same instance
        reuses a program. ``differentiable`` is folded in only when
        True (key neutrality for the default)."""
        from ..aot import op_signature
        sig = (self.solver, int(self.niter), float(self.tol),
               float(self.damp), str(np.dtype(self.dtype)),
               op_signature(self.operator),
               None if self.M is None else ("M", id(self.M)))
        if self.differentiable:
            sig = sig + ("differentiable",)
        return sig


@dataclass
class BlockOutcome:
    """One packed solve, already sliced back to the real fill: ``x``
    is ``(M, k)`` (padding columns dropped), ``statuses`` one name per
    real column (``converged``/``maxiter``/``breakdown``)."""
    x: np.ndarray
    iiter: int
    statuses: Tuple[str, ...]
    k: int                        # real fill
    bucket: int                   # compiled width actually run
    wall_s: float
    # seconds in each of the pool's stages: stage_in, solve, pull
    stage_s: Dict[str, float] = field(default_factory=dict)


def _column_statuses(kold: np.ndarray, tol: float) -> Tuple[str, ...]:
    """Per-column verdict from the final per-column residual scalars:
    non-finite → breakdown, at/under tolerance → converged, else
    maxiter. (With guards on the solver additionally froze breakdown
    columns in-loop; this classification agrees with the recorded
    verdicts for the finite/non-finite split.)"""
    kold = np.atleast_1d(np.asarray(kold))
    out = []
    for v in kold:
        if not np.isfinite(v):
            out.append("breakdown")
        elif v < tol:
            out.append("converged")
        else:
            out.append("maxiter")
    return tuple(out)


class WarmPool:
    """Registry of servable families + the packed-solve entry point.

    Thread-safe for one solve at a time (an internal lock — the
    dispatcher is single-threaded, but drain paths and tests may race
    it). ``warmed`` records every (family, bucket) pair that has been
    through a compile, whether by :meth:`prewarm` or by live traffic.
    """

    def __init__(self, buckets: Optional[Sequence[int]] = None):
        self._families: Dict[str, FamilySpec] = {}
        self._buckets = tuple(sorted(set(buckets))) if buckets \
            else k_buckets()
        self._lock = threading.Lock()
        self.warmed: set = set()

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    @property
    def k_max(self) -> int:
        return self._buckets[-1]

    def register(self, spec: FamilySpec) -> FamilySpec:
        if spec.name in self._families:
            raise ValueError(f"family {spec.name!r} already registered")
        self._families[spec.name] = spec
        return spec

    def family(self, name: str) -> FamilySpec:
        try:
            return self._families[name]
        except KeyError:
            raise KeyError(
                f"unknown operator family {name!r}; registered: "
                f"{sorted(self._families)}") from None

    def families(self) -> Tuple[str, ...]:
        return tuple(sorted(self._families))

    # ------------------------------------------------------------ solve
    def solve(self, name: str, Y: np.ndarray,
              batch: Optional[int] = None) -> BlockOutcome:
        """Solve ``Y``'s ``k`` columns as one padded block program of
        the next-larger bucket width. ``Y`` is ``(N, k)`` (a 1-D ``y``
        is treated as ``k=1``). Three stage spans — ``serve.stage_in``,
        ``serve.solve``, ``serve.pull`` — carry ``batch``, the
        dispatcher's batch number, and their seconds come back in
        ``BlockOutcome.stage_s``."""
        from ..solvers.block import block_cg, block_cgls
        spec = self.family(name)
        ids = {} if batch is None else {"batch": batch}
        t0 = time.monotonic()
        with _trace.span("serve.stage_in", cat="serving", **ids):
            Y = np.asarray(Y, dtype=np.dtype(spec.dtype))
            if Y.ndim == 1:
                Y = Y[:, None]
            N, k = Y.shape
            if N != spec.nrows:
                raise ValueError(
                    f"family {name!r} expects data length {spec.nrows}, "
                    f"got {N}")
            bucket = bucket_for(k, self._buckets)
            if k > bucket:
                raise ValueError(
                    f"fill {k} exceeds the largest bucket {bucket}; "
                    "dispatch at most k_max columns per batch")
            if bucket > k:
                Y = np.concatenate(
                    [Y, np.zeros((N, bucket - k), dtype=Y.dtype)], axis=1)
            yb = DistributedArray(global_shape=(N, bucket),
                                  dtype=np.dtype(spec.dtype))
            yb[:] = Y
        t1 = time.monotonic()
        # the wrappers return host values (``int(iiter)``, the cost
        # history), so the answer is ready on the device at span exit
        with self._lock, _trace.span("serve.solve", cat="serving",
                                     family=name, fill=k, bucket=bucket,
                                     solver=spec.solver, **ids):
            if spec.solver == "cg":
                xb, iiter, cost = block_cg(
                    spec.operator, yb, niter=spec.niter, tol=spec.tol,
                    M=spec.M)
                kold = np.asarray(cost)[-1] ** 2
            else:
                xb, _istop, iiter, kold, _r2, _cost = block_cgls(
                    spec.operator, yb, niter=spec.niter,
                    damp=spec.damp, tol=spec.tol, M=spec.M)
        t2 = time.monotonic()
        with _trace.span("serve.pull", cat="serving", **ids):
            x = np.asarray(xb.array)[:, :k]
            statuses = _column_statuses(kold, spec.tol)[:k]
        t3 = time.monotonic()
        self.warmed.add((name, bucket))
        _WARMED_SIGS.add((spec.signature(), bucket))
        _metrics.inc("serve.pool.solves")
        _metrics.observe("serve.batch.fill", k / bucket)
        return BlockOutcome(x=x, iiter=int(iiter), statuses=statuses,
                            k=k, bucket=bucket, wall_s=t2 - t1,
                            stage_s={"stage_in": t1 - t0, "solve": t2 - t1,
                                     "pull": t3 - t2})

    # ---------------------------------------------------------- prewarm
    def prewarm(self, names: Optional[Sequence[str]] = None,
                widths: Optional[Sequence[int]] = None) -> Dict:
        """Compile (family, bucket) programs before traffic arrives.

        Buckets per family: the explicit ``widths``, each rounded up
        to a configured bucket; else EVERY configured bucket (any fill
        can arrive). Each compile is a zero-RHS solve: the loop
        condition is false at entry, so the cost is exactly one
        compilation, zero iterations. Returns
        ``{family: [buckets compiled]}``.

        Prewarm is keyed on the family SIGNATURE (shape/dtype/solver
        config — :meth:`FamilySpec.signature`), not the operator
        instance id: with the AOT tier armed
        (``PYLOPS_MPI_TPU_AOT``), a (signature, bucket) pair that
        already went through a compile in this process is skipped
        outright — a restarted daemon registering a FRESH operator
        instance for an identical program stops paying a silent
        recompile per bucket. (Without the AOT tier the executables
        live only in the id-keyed fused cache, so an instance change
        genuinely requires the recompile and the zero-RHS solve runs
        as before.) With a banked AOT cache on disk, the zero-RHS
        solves themselves load serialized executables in milliseconds
        instead of compiling."""
        from ..aot import aot_enabled
        report: Dict[str, list] = {}
        if widths is not None:
            want = [bucket_for(w, self._buckets) for w in widths]
        else:
            want = list(self._buckets)
        for name in (names if names is not None else self.families()):
            spec = self.family(name)
            sig = spec.signature() if aot_enabled() else None
            done = []
            for b in sorted(set(want)):
                if sig is not None and (sig, b) in _WARMED_SIGS:
                    # identical program already compiled (or banked)
                    # in this process — the signature-keyed AOT tier
                    # serves it to the new instance without a compile
                    self.warmed.add((name, b))
                    done.append(b)
                    _metrics.inc("serve.pool.prewarm_skipped")
                    _trace.event("serve.prewarm_skip", cat="serving",
                                 family=name, bucket=b)
                    continue
                with _trace.span("serve.prewarm", cat="serving",
                                 family=name, bucket=b):
                    self.solve(name, np.zeros((spec.nrows, b),
                                              dtype=np.dtype(spec.dtype)))
                done.append(b)
                _metrics.inc("serve.pool.prewarmed")
            report[name] = done
        return report
