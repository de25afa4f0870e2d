"""CG / CGLS distributed solvers.

Rebuild of ``pylops_mpi/optimization/cls_basic.py`` (CG ``12-249``, CGLS
``252-531``) and the functional wrappers ``optimization/basic.py``.

Two execution paths:

- **class API** (`CG`, `CGLS`): reference-parity ``setup/step/run/
  finalize/solve`` with per-iteration ``callback`` hooks. Each step is a
  handful of fused XLA ops; scalars stay on device (no per-iteration
  ``.item()`` host syncs — the reference pulls 4 scalars/iter,
  ref ``cls_basic.py:389-401``).
- **fused path** (functional ``cg``/``cgls`` with ``fused=True``,
  default): the whole iteration runs as one ``lax.while_loop`` under
  ``jit`` — matvec, rmatvec and the dot-product ``psum``s compile into a
  single XLA program per solve; the cost history is carried in a
  fixed-length on-device trace buffer (SURVEY §7 hard-part: host-synced
  solver scalars).

Reference quirk preserved: CGLS ``setup`` damps the initial residual by
``damp`` while ``step`` uses ``damp**2`` (ref ``cls_basic.py:345-350`` vs
``392-393``); immaterial for the usual ``x0 = 0``.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..distributedarray import DistributedArray, Partition
from ..stacked import StackedDistributedArray
from ..diagnostics import metrics as _metrics
from ..diagnostics import telemetry, trace as _trace

__all__ = ["CG", "CGLS", "cg", "cgls", "cg_guarded", "cgls_guarded",
           "clear_fused_cache"]

Vector = Union[DistributedArray, StackedDistributedArray]


def _abs(v):
    return jnp.abs(jnp.asarray(v))


def _vdtype(v):
    """Element dtype of a (possibly nested-stacked) distributed
    vector."""
    if isinstance(v, StackedDistributedArray):
        return np.result_type(*[_vdtype(d) for d in v.distarrays])
    return v.dtype


def _rdot(u, v):
    """Recurrence dot product at the policy reduction dtype: the
    squared-norm scalars (``k``, ``cOpc``, ``q·q``) must accumulate at
    f32 or better even when the carry vectors are narrower — a bf16
    ``k/kold`` ratio is the recurrence contamination behind the round-5
    bf16 cliff (ops/_precision.py module doc). For ≥f32 carries this is
    exactly the old ``_abs(u.dot(v.conj()))``.

    The result passes through ``collectives.reduce_stall`` — a no-op
    (nothing traced) unless the ``PYLOPS_MPI_TPU_REDUCE_STALL`` latency
    seam is armed, in which case every reduction result drags an N-step
    serial dependency chain: the bench's stand-in for per-collective
    wire latency on a real fabric (docs/ca.md)."""
    from ..ops._precision import reduction_dtype
    from ..parallel.collectives import reduce_stall
    return reduce_stall(
        _abs(u.dot(v.conj())).astype(reduction_dtype(_vdtype(u))))


def _step_scalar(s, carry_dtype):
    """Cast a recurrence scalar for a vector update so the CARRY dtype
    survives the multiply: a wide (f32) step scalar times a narrow
    (bf16) carry would promote the carry and break the while_loop's
    fixed pytree dtypes. Real scalars against complex carries pass
    through (no promotion)."""
    dt = np.dtype(carry_dtype)
    if np.issubdtype(dt, np.complexfloating):
        return s
    return s.astype(dt)


def _cast_vec(v, dt):
    """Cast a (possibly stacked) distributed vector to ``dt`` without
    leaving the jit trace — used to pin a preconditioner's output back
    to the carry dtype so the while_loop pytree dtypes stay fixed."""
    if isinstance(v, StackedDistributedArray):
        return StackedDistributedArray(
            [_cast_vec(d, dt) for d in v.distarrays])
    return DistributedArray._wrap(v._arr.astype(dt), v)


def _precond_apply(M, r, xdt):
    """Apply the preconditioner seam: ``z = M⁻¹ r`` (``M.matvec`` — the
    preconditioner operator IS the approximate inverse), cast back to
    the carry dtype. ``M=None`` returns ``r`` ITSELF — not a copy, not
    a new op — so the unpreconditioned trace is the literally unchanged
    pre-seam program (the ``M=None`` HLO bit-identity pin,
    tests/test_precond.py)."""
    if M is None:
        return r
    z = M.matvec(r)
    if np.dtype(_vdtype(z)) != np.dtype(xdt):
        z = _cast_vec(z, np.dtype(xdt))
    return z


def _precond_signature(M) -> str:
    """Stable identity of a preconditioner CONFIGURATION (not instance)
    — what segmented checkpoints bank so a resume with a different M
    refuses instead of silently mixing trajectories."""
    if M is None:
        return "none"
    sig = getattr(M, "precond_signature", None)
    if callable(sig):
        return str(sig())
    return f"{type(M).__name__}{tuple(M.shape)}"


def _mkey(M):
    """Fused-cache key component for the preconditioner: EMPTY when
    ``M=None`` so every pre-seam cache key is byte-identical to before
    the seam existed (zero new cache entries for unpreconditioned
    solves)."""
    return () if M is None else (("M", id(M)),)


def _mp_floor(k0):
    """Machine-precision floor for the solver's squared recurrence
    norm — ``k = |r|²`` for CG, ``k = |Aᴴr|²`` for CGLS: once ``k``
    falls below ``(100·eps)²·k0`` further updates are numerical noise. The fused loops FREEZE the recurrence there (zero
    step + zero momentum) instead of exiting: iterating past this point
    is not just useless, it is unstable — the ``k/kold`` ratio of
    noise-level quantities can drift above 1 and pump the recurrence
    exponentially (observed: a 5-shard ragged CGLS at tol=0 reached
    1e13 error by iteration 400 while NumPy's trajectory happened to
    hit an exact fixed point). Freezing (rather than early exit) keeps
    the iteration count — and the per-iteration work the benchmarks
    time — exactly as requested."""
    k0 = jnp.asarray(k0)
    eps = jnp.finfo(k0.dtype).eps
    return k0 * (100 * eps) ** 2


class _BaseSolver:
    def __init__(self, Op):
        self.Op = Op
        self.callback = lambda x: None
        self.tstart = time.time()

    def _callback_wrap(self, callback):
        if callback is not None:
            self.callback = callback

    def memory_usage(self) -> None:
        """No-op hook, reference Solver-ABC parity
        (ref ``cls_basic.py:54-55``)."""


class CG(_BaseSolver):
    """Conjugate gradient for square distributed operators
    (ref ``cls_basic.py:12-249``).

    The ``setup``/``step``/``run`` class API exists for callback /
    per-iteration-inspection parity with the reference and syncs 2-3
    scalars to host EVERY iteration — it is the slow path. The
    functional :func:`cg` (fused ``lax.while_loop``, default when no
    callbacks) is the fast path."""

    def setup(self, y: Vector, x0: Vector, niter: Optional[int] = None,
              tol: float = 1e-4, show: bool = False) -> Vector:
        self.y = y
        self.tol = tol
        self.niter = niter
        x = x0.copy()
        self.r = self.y - self.Op.matvec(x)
        self.c = self.r.copy()
        self.kold = _abs(self.r.dot(self.r.conj()))
        self.cost = [jnp.sqrt(self.kold)]
        self.iiter = 0
        if show:
            self._print_setup()
        return x

    def step(self, x: Vector, show: bool = False) -> Vector:
        """One CG step (ref ``cls_basic.py:112-141``); α/β stay on
        device."""
        Opc = self.Op.matvec(self.c)
        cOpc = _abs(self.c.dot(Opc.conj()))
        a = self.kold / cOpc
        x = x + self.c * a
        self.r = self.r - Opc * a
        k = _abs(self.r.dot(self.r.conj()))
        b = k / self.kold
        self.c = self.r + self.c * b
        self.kold = k
        self.iiter += 1
        self.cost.append(jnp.sqrt(self.kold))
        telemetry.iteration("cg", self.iiter, resid=jnp.sqrt(k), k=k)
        if show:
            self._print_step(x)
        return x

    def run(self, x: Vector, niter: Optional[int] = None,
            show: bool = False, itershow=(10, 10, 10)) -> Vector:
        niter = self.niter if niter is None else niter
        if niter is None:
            raise ValueError("niter must not be None")
        while self.iiter < niter and float(jnp.max(self.kold)) > self.tol:
            showstep = show and (self.iiter < itershow[0]
                                 or niter - self.iiter < itershow[1]
                                 or self.iiter % itershow[2] == 0)
            x = self.step(x, showstep)
            self.callback(x)
        return x

    def finalize(self, show: bool = False) -> None:
        self.tend = time.time()
        self.telapsed = self.tend - self.tstart
        self.cost = np.asarray(jnp.stack(self.cost))

    def solve(self, y: Vector, x0: Vector, niter: int = 10, tol: float = 1e-4,
              show: bool = False, itershow=(10, 10, 10)
              ) -> Tuple[Vector, int, np.ndarray]:
        x = self.setup(y=y, x0=x0, niter=niter, tol=tol, show=show)
        x = self.run(x, niter, show=show, itershow=itershow)
        self.finalize(show)
        return x, self.iiter, self.cost

    def _print_setup(self):
        print(f"CG\ntol = {self.tol:10e}\tniter = {self.niter}")

    def _print_step(self, x):
        print(f"{self.iiter:6g}        {float(jnp.max(self.cost[self.iiter])):11.4e}")


class CGLS(_BaseSolver):
    """Damped least-squares CGLS (ref ``cls_basic.py:252-531``).

    Like :class:`CG`, the ``setup``/``step``/``run`` API is the
    host-synced slow path, provided for callback parity; the functional
    :func:`cgls` (fused ``lax.while_loop``) is the fast path."""

    def setup(self, y: Vector, x0: Vector, niter: Optional[int] = None,
              damp: float = 0.0, tol: float = 1e-4,
              show: bool = False) -> Vector:
        self.y = y
        self.damp = damp ** 2
        self.tol = tol
        self.niter = niter
        x = x0.copy()
        self.s = self.y - self.Op.matvec(x)
        # ref cls_basic.py:347-349 uses un-squared damp here (see module doc)
        r = self.Op.rmatvec(self.s) - x * damp
        self.c = r.copy()
        self.q = self.Op.matvec(self.c)
        self.kold = _abs(r.dot(r.conj()))
        self.cost = [jnp.asarray(self.s.norm())]
        self.cost1 = [jnp.sqrt(self.cost[0] ** 2
                               + self.damp * _abs(x.dot(x.conj())))]
        self.iiter = 0
        if show:
            self._print_setup()
        return x

    def step(self, x: Vector, show: bool = False) -> Vector:
        """One CGLS step (ref ``cls_basic.py:373-404``)."""
        a = _abs(self.kold / (self.q.dot(self.q.conj())
                              + self.damp * self.c.dot(self.c.conj())))
        x = x + self.c * a
        self.s = self.s - self.q * a
        r = self.Op.rmatvec(self.s) - x * self.damp
        k = _abs(r.dot(r.conj()))
        b = k / self.kold
        self.c = r + self.c * b
        self.q = self.Op.matvec(self.c)
        self.kold = k
        self.iiter += 1
        self.cost.append(jnp.asarray(self.s.norm()))
        self.cost1.append(jnp.sqrt(self.cost[self.iiter] ** 2
                                   + self.damp * _abs(x.dot(x.conj()))))
        telemetry.iteration("cgls", self.iiter,
                            resid=self.cost[self.iiter], k=k)
        if show:
            self._print_step(x)
        return x

    def run(self, x: Vector, niter: Optional[int] = None,
            show: bool = False, itershow=(10, 10, 10)) -> Vector:
        niter = self.niter if niter is None else niter
        if niter is None:
            raise ValueError("niter must not be None")
        while self.iiter < niter and float(jnp.max(self.kold)) > self.tol:
            showstep = show and (self.iiter < itershow[0]
                                 or niter - self.iiter < itershow[1]
                                 or self.iiter % itershow[2] == 0)
            x = self.step(x, showstep)
            self.callback(x)
        return x

    def finalize(self, show: bool = False) -> None:
        self.tend = time.time()
        self.telapsed = self.tend - self.tstart
        self.istop = 1 if float(jnp.max(self.kold)) < self.tol else 2
        self.r1norm = self.kold
        self.r2norm = self.cost1[self.iiter]
        self.cost = np.asarray(jnp.stack(self.cost))
        self.cost1 = np.asarray(jnp.stack(self.cost1))

    def solve(self, y: Vector, x0: Vector, niter: int = 10, damp: float = 0.0,
              tol: float = 1e-4, show: bool = False, itershow=(10, 10, 10)
              ) -> Tuple[Vector, int, int, jax.Array, jax.Array, np.ndarray]:
        x = self.setup(y=y, x0=x0, niter=niter, damp=damp, tol=tol, show=show)
        x = self.run(x, niter, show=show, itershow=itershow)
        self.finalize(show)
        return x, self.istop, self.iiter, self.r1norm, self.r2norm, self.cost

    def _print_setup(self):
        print(f"CGLS\ntol = {self.tol:10e}\tniter = {self.niter}")

    def _print_step(self, x):
        print(f"{self.iiter:6g}        {float(jnp.max(self.cost[self.iiter])):11.4e}")


# --------------------------------------------------------- fused (on-device)
# Builder calling convention (shared by _get_fused and every fused
# loop below): all runtime operands are POSITIONAL with the model
# vector second — ``fn(y, x0, ...)`` — so donation can address it by
# argnum. ``x0`` is donated (``_DONATE_X0``): the loop carry starts in
# the caller's buffer instead of a program-entry copy, which is why
# the builders bind the carry as ``x = x0`` (a traced ``x0.copy()``
# would be exactly the copy-of-donated-state the HLO pin forbids —
# tests/test_precision.py::test_fused_cgls_donation). Where the loop
# holds ``x`` N-D (``_while_carried``) the donation still holds — the
# program's ``input_output_alias`` pairs ``x0`` with the flat ``x`` it
# returns, compiled for a v5e as on the CPU — but the carry is then the
# entry's relayout of that buffer, not the buffer in place: the exit's
# flatten writes the answer back into it.
#
# In-loop guards (ISSUE 6): every builder takes a static ``guards``
# flag. ``guards=False`` (the default, and the only mode when
# ``PYLOPS_MPI_TPU_GUARDS`` is off) traces EXACTLY the pre-guard
# program — bit-identical lowered HLO, pinned by the resilience
# suite. ``guards=True`` appends a ``(status, bestk, stall)`` guard
# carry computed purely from the recurrence scalars the loop already
# holds (zero host callbacks): NaN/Inf in the step/momentum/norm
# scalars or a denominator underflow reject the poisoned update (the
# carry keeps the LAST FINITE iterate) and exit with
# ``status=BREAKDOWN``; ``stall_n`` iterations without a new best
# residual exit with ``status=STAGNATION`` (the machine-precision
# freeze below is excluded — parked at the floor is done, not sick).
_DONATE_X0 = (1,)


def _i32(v):
    return jnp.asarray(v, dtype=jnp.int32)


def _reject(bad, old, new):
    """``old`` where ``bad`` else ``new``, elementwise over a
    (possibly stacked) distributed vector — the guard carries keep the
    last finite iterate by rejecting a poisoned update wholesale
    (scaling the step to zero would not do: ``NaN * 0`` is ``NaN``)."""
    if isinstance(new, StackedDistributedArray):
        return StackedDistributedArray(
            [_reject(bad, o, n)
             for o, n in zip(old.distarrays, new.distarrays)])
    return DistributedArray._wrap(jnp.where(bad, old._arr, new._arr), new)


def _guard_update(status, bestk, stall, bad, k, done, stall_n: int):
    """One guard-carry step, shared by every guarded body: breakdown
    beats stagnation; the stall counter only runs while the recurrence
    is live (not poisoned, not frozen at the machine-precision
    floor)."""
    from ..resilience import status as _rstatus
    kmax = jnp.max(k)
    improved = (kmax < bestk) & ~bad
    frozen = jnp.all(done)
    stall = jnp.where(bad | frozen, stall,
                      jnp.where(improved, jnp.zeros_like(stall),
                                stall + 1))
    bestk = jnp.where(improved, kmax, bestk)
    status = jnp.where(bad, _i32(_rstatus.BREAKDOWN),
                       jnp.where(stall >= stall_n,
                                 _i32(_rstatus.STAGNATION), status))
    return status, bestk, stall


def _resolve_status(status, kold, tol):
    """Post-loop status resolution (still on device): a loop that
    exited without a guard verdict either converged or ran out of
    iterations."""
    from ..resilience import status as _rstatus
    return jnp.where(
        status != _rstatus.RUNNING, status,
        jnp.where(jnp.max(kold) <= tol, _i32(_rstatus.CONVERGED),
                  _i32(_rstatus.MAXITER)))


def _fault_sites(guards: bool, fault):
    """Static (nan_at, stall_at) injection iterations for a guarded
    body — both ``None`` (nothing traced) unless a chaos fault is
    armed (resilience/faults.py)."""
    if not guards or not fault:
        return None, None
    if fault.get("kind") == "nan":
        return fault["iteration"], None
    if fault.get("kind") == "stall":
        return None, fault["iteration"]
    return None, None


# Shaped carries. The solvers' vectors are flat; an operator that works
# on an N-D cube reshapes at its edges, and on a TPU a flat
# ``f32[V]{T(1024)}`` against a tiled ``(ny, nx, nt){T(8,128)}`` cube is
# a relayout COPY of the whole vector each time (seven an iteration,
# 17 of 70 ms, in the stacked post-stack system; PERF.md section 6,
# PR 37). So the fused loops hold each vector of their carry in the
# shape its operator declares (``dims`` model side, ``dimsd`` data
# side): one reshape at the loop's entry, one at its exit, and the
# UNCHANGED flat body between ``flatten`` and ``shape`` — XLA cancels
# every ``reshape(-1)`` / ``reshape(dims)`` pair at the operators'
# edges. The shape is data read from the operator and the vector; no
# keyword, no environment variable, no operator's class name.
_LANES = 128  # a TPU tile's minor extent: a narrower minor axis pads


def _answers(got):
    """``[(shape, why), ...]`` as one answer: the shapes as a list and
    the first ``why`` of a vector left flat."""
    return [s for s, _ in got], next((w for _, w in got if w), None)


def _carry_shape(v: Vector, dims):
    """``(shape, why)`` for one vector of a fused loop's carry: the
    N-D shape to hold it in given what the operator declares for its
    side, or ``None`` (flat, the program as it was) with the one word
    ``solver.carry_select`` gives as ``why``:

    - ``columns``: the vector has columns (``ndim == 2``);
    - ``undeclared``: ``dims`` is not two or more axes that multiply to
      the vector's length (a stacked vector wants one such tuple a
      component);
    - ``lanes``: folding trailing axes together until the minor extent
      is a multiple of 128 lanes leaves one axis (``(1023, 4096, 16)``
      folds to ``(1023, 65536)``; a 16-lane minor axis would pad 8 x);
    - ``ragged``: a ``SCATTER`` vector whose shards are not the
      balanced, unpadded split into whole leading rows (``BROADCAST``
      vectors are whole on every shard).

    A ``StackedDistributedArray`` answers by component, as a list."""
    if isinstance(v, StackedDistributedArray):
        by_part = (isinstance(dims, (tuple, list))
                   and len(dims) == v.narrays
                   and all(isinstance(d, (tuple, list)) for d in dims))
        return _answers([_carry_shape(d, p) for d, p in zip(
            v.distarrays, dims if by_part else (None,) * v.narrays)])
    if v.ndim != 1:
        return None, "columns"
    if (not isinstance(dims, (tuple, list)) or len(dims) < 2
            or not all(isinstance(d, (int, np.integer)) for d in dims)
            or int(np.prod(dims, dtype=np.int64)) != v.size):
        return None, "undeclared"
    shape = tuple(int(d) for d in dims)
    while len(shape) > 1 and shape[-1] % _LANES:
        shape = shape[:-2] + (shape[-2] * shape[-1],)
    if len(shape) == 1:
        return None, "lanes"
    if v.partition == Partition.SCATTER:
        rows, rem = divmod(shape[0], v.n_shards)
        if rem or v._axis_sizes != (rows * (v.size // shape[0]),) \
                * v.n_shards:
            return None, "ragged"
    return shape, None


def _shaped(shape) -> bool:
    """Does an answer of ``_carry_shape`` (a shape, ``None``, or a list
    of answers) hold any vector N-D?"""
    if isinstance(shape, list):
        return any(map(_shaped, shape))
    return shape is not None


def _carry_shapes(Op, vectors, sides):
    """The one place that decides the shapes of a fused loop's carry:
    for each of ``vectors`` (the loop's leading state, or the caller's
    ``(x0, y)``) the answer of :func:`_carry_shape` against the
    operator's ``dims`` (side ``"dims"``: model vectors) or ``dimsd``
    (data vectors), and the first ``why`` of a vector left flat.
    Decided a vector, not a side: ``x`` lies as the caller split it,
    ``c`` and ``q`` as the operator does."""
    return _answers([_carry_shape(v, getattr(Op, side, None))
                     for v, side in zip(vectors, sides)])


def _hold(v: Vector, shape) -> Vector:
    """``v`` as the loop carries it: an N-D ``DistributedArray`` split
    along axis 0 over the same shards (the reshape of a balanced flat
    split into whole rows moves nothing), or ``v`` itself."""
    if isinstance(v, StackedDistributedArray):
        return StackedDistributedArray(
            [_hold(d, s) for d, s in zip(v.distarrays, shape)])
    if shape is None:
        return v
    nd = DistributedArray(global_shape=shape, mesh=v.mesh,
                          partition=v.partition, axis=0, mask=v.mask,
                          dtype=v.dtype)
    nd[:] = v._arr.reshape(shape)
    return nd


def _unhold(v: Vector, shape) -> Vector:
    """The flat vector the recurrence and every operator work on."""
    if isinstance(v, StackedDistributedArray):
        return StackedDistributedArray(
            [_unhold(d, s) for d, s in zip(v.distarrays, shape)])
    return v if shape is None else v.ravel()


def _while_carried(solver: str, Op, cond, body, state, sides):
    """The one rule that launches a fused loop: ``lax.while_loop`` over
    ``state`` whose leading ``len(sides)`` entries are the vectors,
    each held in the shape :func:`_carry_shapes` answers and handed to
    the unchanged flat ``cond`` / ``body`` through ``flatten``. All
    flat is ``lax.while_loop(cond, body, state)`` itself: the program
    as it was, byte for byte. ``solver.carry_select`` (``solver``,
    ``shaped`` 0/1, ``model`` and ``data`` — the shapes of ``x`` and of
    the first data-side vector —, a one-word ``why`` for what stayed
    flat) says which under ``PYLOPS_MPI_TPU_TRACE``, once a trace."""
    n = len(sides)
    shapes, why = _carry_shapes(Op, state[:n], sides)
    data = [s for s, side in zip(shapes, sides) if side == "dimsd"]
    _trace.event("solver.carry_select", cat="schedule", solver=solver,
                 shaped=int(_shaped(shapes)), model=shapes[0],
                 data=data[0] if data else None,
                 **({"why": why} if why else {}))
    if not _shaped(shapes):
        return lax.while_loop(cond, body, state)

    def shape(st):
        return tuple(map(_hold, st[:n], shapes)) + tuple(st[n:])

    def flatten(st):
        return tuple(map(_unhold, st[:n], shapes)) + tuple(st[n:])

    return flatten(lax.while_loop(lambda st: cond(flatten(st)),
                                  lambda st: shape(body(flatten(st))),
                                  shape(state)))


_CG_SIDES = ("dims",) * 3   # CG's (x, r, c): a square operator's model


def _carry_tag(Op, x0: Vector, y: Optional[Vector], fused: bool) -> str:
    """The ``carry`` tag of the ``pmt.solver.cgls`` / ``cg`` spans:
    ``shaped`` where the fused loop would hold the caller's vectors
    N-D, else ``flat`` (the host's reading of the rule the traced
    program applies; the classic fused loops', as ``normal`` is)."""
    vectors, sides = ((x0,), ("dims",)) if y is None \
        else ((x0, y), ("dims", "dimsd"))
    return "shaped" if fused and _shaped(
        _carry_shapes(Op, vectors, sides)[0]) else "flat"


def _make_cg_body(Op, xdt, floors, *, M=None, guards=False,
                  carry_status=False, stall_n=0, fault=None):
    """CG loop body over the carry ``(x, r, c, kold, iiter, cost
    [, status][, bestk, stall])`` — the one implementation behind the
    single-shot fused loop, the guarded variant and the segmented
    epoch program. ``carry_status`` threads the status word without
    the detectors (the segmented path always carries it so resumed
    epochs keep one pytree).

    ``M`` is the preconditioner seam (PCG): ``z = M r`` replaces ``r``
    in the recurrence norm (``kold = r·z``) and the direction update
    (``c = z + b c``) — the TRUE residual ``r`` stays in the carry, so
    the carry pytree (shapes, dtypes, donation aliasing) is identical
    with and without M, and ``M=None`` traces the exact
    unpreconditioned program (``z`` IS ``r``)."""
    from ..resilience import faults as _faults
    nan_at, stall_at = _fault_sites(guards, fault)

    def body(state):
        if guards:
            x, r, c, kold, iiter, cost, status, bestk, stall = state
        elif carry_status:
            x, r, c, kold, iiter, cost, status = state
        else:
            x, r, c, kold, iiter, cost = state
        done = kold <= floors
        Opc = Op.matvec(c)
        if nan_at is not None:
            Opc = _faults.inject_nan(Opc, iiter, nan_at)
        a = kold / _rdot(c, Opc)
        a = jnp.where(done, jnp.zeros_like(a), a)
        if stall_at is not None:
            a = _faults.inject_stall(a, iiter, stall_at)
        xn = x + c * _step_scalar(a, xdt)
        rn = r - Opc * _step_scalar(a, xdt)
        zn = _precond_apply(M, rn, xdt)
        k = _rdot(rn, zn)
        k = jnp.where(done, kold, k)
        b = jnp.where(done, jnp.zeros_like(k), k / kold)
        cn = zn + c * _step_scalar(b, xdt)
        if guards:
            bad = (jnp.any(~jnp.isfinite(a)) | jnp.any(~jnp.isfinite(k))
                   | jnp.any(~jnp.isfinite(b)))
            x = _reject(bad, x, xn)
            r = _reject(bad, r, rn)
            c = _reject(bad, c, cn)
            k = jnp.where(bad, kold, k)
            status, bestk, stall = _guard_update(status, bestk, stall,
                                                 bad, k, done, stall_n)
        else:
            x, r, c = xn, rn, cn
        iiter = iiter + 1
        cost = lax.dynamic_update_index_in_dim(cost, jnp.sqrt(k), iiter, 0)
        # no-op unless telemetry is enabled (PYLOPS_MPI_TPU_TRACE=full):
        # disabled builds trace NOTHING here — the zero-host-callback pin
        telemetry.iteration("cg", iiter, resid=jnp.sqrt(k), k=k, alpha=a)
        if guards:
            return (x, r, c, k, iiter, cost, status, bestk, stall)
        if carry_status:
            return (x, r, c, k, iiter, cost, status)
        return (x, r, c, k, iiter, cost)

    return body


def _cg_fused(Op, y: Vector, x0: Vector, tol, *, niter: int, M=None,
              guards: bool = False, stall_n: int = 0, fault=None):
    """Whole CG solve as one ``lax.while_loop`` (SURVEY §3.2: the
    reference's hot loop does 4 host-synced allreduces per iteration —
    here everything fuses into a single XLA program). Recurrence
    scalars accumulate at the policy reduction dtype (``_rdot``) and
    re-enter vector updates at the carry dtype (``_step_scalar``) so
    the carry pytree dtypes are identical at iteration 1 and k.
    ``M`` preconditions (PCG — see :func:`_make_cg_body`);
    ``guards=True`` returns an extra status word (see the section
    comment above)."""
    xdt = _vdtype(x0)
    x = x0  # donated: the carry aliases the caller's buffer in place
    r = y - Op.matvec(x)
    z = _precond_apply(M, r, xdt)
    c = z
    kold = _rdot(r, z)
    floors = _mp_floor(kold)
    cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold), dtype=jnp.asarray(kold).dtype)
    cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold), 0, 0)
    body = _make_cg_body(Op, xdt, floors, M=M, guards=guards,
                         stall_n=stall_n, fault=fault)
    if guards:
        from ..resilience import status as _rstatus
        state = (x, r, c, kold, jnp.asarray(0), cost0,
                 _i32(_rstatus.RUNNING), jnp.max(kold), _i32(0))

        def cond(state):
            return ((state[4] < niter) & (jnp.max(state[3]) > tol)
                    & (state[6] == _rstatus.RUNNING))

        x, r, c, kold, iiter, cost, status, _, _ = \
            _while_carried("cg", Op, cond, body, state, _CG_SIDES)
        return x, iiter, cost, _resolve_status(status, kold, tol)

    def cond(state):
        _, _, _, kold, iiter, _ = state
        return (iiter < niter) & (jnp.max(kold) > tol)

    state = (x, r, c, kold, jnp.asarray(0), cost0)
    x, r, c, kold, iiter, cost = _while_carried("cg", Op, cond, body,
                                                state, _CG_SIDES)
    return x, iiter, cost


def _make_cgls_body(Op, xdt, damp2, floors, *, M=None, normal=False,
                    fresh=False, guards=False, carry_status=False,
                    stall_n=0, fault=None):
    """CGLS loop body (classic two-sweep, fused-normal, or fresh) over
    the carry ``(x, s, c, q, ...)`` / ``(x, s, r, c, ...)`` / ``(x, s,
    c, ...)`` — shared by the single-shot loops, the guarded variants
    and the segmented epoch program (solvers/segmented.py). ``M``
    preconditions the NORMAL equations (PCGLS): it should approximate
    ``(OpᴴOp + damp²)⁻¹``; applied to the normal residual in every
    schedule, carries unchanged, ``M=None`` bit-identical (see
    :func:`_make_cg_body`).

    ``fresh`` (the one-sweep schedule on an operator that offers
    ``fresh_normal_matvec``, ``has_fresh_normal``): the normal residual
    is not carried but made anew each iteration, ``r = Opᴴ s_{k+1} −
    damp² x_{k+1}``, with ``Opᴴ s_{k+1} = Opᴴ s_k − a Opᴴ q_k`` taken
    from the SAME read of the operator that gave ``q_k = Op c_k``. In
    exact arithmetic it is the fused-normal body's ``r ← r − a(u +
    damp² c)``; in float32 that recurrence accumulates its rounding
    iteration after iteration, and on an operator whose singular values
    span the band of a wavelet (``MPIMDC``: 4e-4 to 0.89) drifts
    2.9e-5 from the plain reference's iterate in 30 iterations, where
    this body stays at the classic schedule's 4e-7 (PERF.md section
    6)."""
    from ..resilience import faults as _faults
    nan_at, stall_at = _fault_sites(guards, fault)

    def body_classic(state):
        if guards:
            x, s, c, q, kold, iiter, cost, cost1, status, bestk, stall \
                = state
        elif carry_status:
            x, s, c, q, kold, iiter, cost, cost1, status = state
        else:
            x, s, c, q, kold, iiter, cost, cost1 = state
        with _trace.span("solver.step"):
            done = kold <= floors
            a = _abs(kold / (_rdot(q, q) + damp2 * _rdot(c, c)))
            a = jnp.where(done, jnp.zeros_like(a), a)
            if stall_at is not None:
                a = _faults.inject_stall(a, iiter, stall_at)
            xn = x + c * _step_scalar(a, xdt)
            sn_ = s - q * _step_scalar(a, xdt)
        r = Op.rmatvec(sn_)
        with _trace.span("solver.direction"):
            r = r - xn * damp2
        z = _precond_apply(M, r, xdt)
        with _trace.span("solver.direction"):
            k = _rdot(r, z)
            k = jnp.where(done, kold, k)
            b = jnp.where(done, jnp.zeros_like(k), k / kold)
            cn = z + c * _step_scalar(b, xdt)
        qn = Op.matvec(cn)
        with _trace.span("solver.direction"):
            if nan_at is not None:
                qn = _faults.inject_nan(qn, iiter, nan_at)
            if guards:
                bad = (jnp.any(~jnp.isfinite(a))
                       | jnp.any(~jnp.isfinite(k))
                       | jnp.any(~jnp.isfinite(b)))
                x = _reject(bad, x, xn)
                s = _reject(bad, s, sn_)
                c = _reject(bad, c, cn)
                q = _reject(bad, q, qn)
                k = jnp.where(bad, kold, k)
                status, bestk, stall = _guard_update(
                    status, bestk, stall, bad, k, done, stall_n)
            else:
                x, s, c, q = xn, sn_, cn, qn
        with _trace.span("solver.cost"):
            iiter = iiter + 1
            sn = jnp.asarray(s.norm())
            cost = lax.dynamic_update_index_in_dim(cost, sn, iiter, 0)
            r2 = jnp.sqrt(sn ** 2 + damp2 * _rdot(x, x))
            cost1 = lax.dynamic_update_index_in_dim(cost1, r2, iiter, 0)
        # no-op unless telemetry is enabled (see _make_cg_body note)
        telemetry.iteration("cgls", iiter, resid=sn, k=k, alpha=a)
        if guards:
            return (x, s, c, q, k, iiter, cost, cost1, status, bestk,
                    stall)
        if carry_status:
            return (x, s, c, q, k, iiter, cost, cost1, status)
        return (x, s, c, q, k, iiter, cost, cost1)

    def body_normal(state):
        if guards:
            x, s, r, c, kold, iiter, cost, cost1, status, bestk, stall \
                = state
        elif carry_status:
            x, s, r, c, kold, iiter, cost, cost1, status = state
        else:
            x, s, r, c, kold, iiter, cost, cost1 = state
        done = kold <= floors
        u, q = Op.normal_matvec(c)
        with _trace.span("solver.step"):
            if nan_at is not None:
                u = _faults.inject_nan(u, iiter, nan_at)
                q = _faults.inject_nan(q, iiter, nan_at)
            a = _abs(kold / (_rdot(q, q) + damp2 * _rdot(c, c)))
            a = jnp.where(done, jnp.zeros_like(a), a)
            if stall_at is not None:
                a = _faults.inject_stall(a, iiter, stall_at)
            xn = x + c * _step_scalar(a, xdt)
            sn_ = s - q * _step_scalar(a, xdt)
            rn = r - (u + c * damp2) * _step_scalar(a, xdt)
        zn = _precond_apply(M, rn, xdt)
        with _trace.span("solver.direction"):
            k = _rdot(rn, zn)
            k = jnp.where(done, kold, k)
            b = jnp.where(done, jnp.zeros_like(k), k / kold)
            cn = zn + c * _step_scalar(b, xdt)
            if guards:
                bad = (jnp.any(~jnp.isfinite(a))
                       | jnp.any(~jnp.isfinite(k))
                       | jnp.any(~jnp.isfinite(b)))
                x = _reject(bad, x, xn)
                s = _reject(bad, s, sn_)
                r = _reject(bad, r, rn)
                c = _reject(bad, c, cn)
                k = jnp.where(bad, kold, k)
                status, bestk, stall = _guard_update(
                    status, bestk, stall, bad, k, done, stall_n)
            else:
                x, s, r, c = xn, sn_, rn, cn
        with _trace.span("solver.cost"):
            iiter = iiter + 1
            sn = jnp.asarray(s.norm())
            cost = lax.dynamic_update_index_in_dim(cost, sn, iiter, 0)
            r2 = jnp.sqrt(sn ** 2 + damp2 * _rdot(x, x))
            cost1 = lax.dynamic_update_index_in_dim(cost1, r2, iiter, 0)
        # no-op unless telemetry is enabled (see _make_cg_body note)
        telemetry.iteration("cgls", iiter, resid=sn, k=k, alpha=a)
        if guards:
            return (x, s, r, c, k, iiter, cost, cost1, status, bestk,
                    stall)
        if carry_status:
            return (x, s, r, c, k, iiter, cost, cost1, status)
        return (x, s, r, c, k, iiter, cost, cost1)

    def body_fresh(state):
        if guards:
            x, s, c, kold, iiter, cost, cost1, status, bestk, stall = state
        elif carry_status:
            x, s, c, kold, iiter, cost, cost1, status = state
        else:
            x, s, c, kold, iiter, cost, cost1 = state
        done = kold <= floors
        q, adjoint = Op.fresh_normal_matvec(c, s)
        with _trace.span("solver.step"):
            if nan_at is not None:
                q = _faults.inject_nan(q, iiter, nan_at)
            a = _abs(kold / (_rdot(q, q) + damp2 * _rdot(c, c)))
            a = jnp.where(done, jnp.zeros_like(a), a)
            if stall_at is not None:
                a = _faults.inject_stall(a, iiter, stall_at)
            xn = x + c * _step_scalar(a, xdt)
            sn_ = s - q * _step_scalar(a, xdt)
        r = adjoint(_step_scalar(a, xdt))       # Opᴴ sn_, from this sweep
        with _trace.span("solver.direction"):
            r = r - xn * damp2
        z = _precond_apply(M, r, xdt)
        with _trace.span("solver.direction"):
            k = _rdot(r, z)
            k = jnp.where(done, kold, k)
            b = jnp.where(done, jnp.zeros_like(k), k / kold)
            cn = z + c * _step_scalar(b, xdt)
            if guards:
                bad = (jnp.any(~jnp.isfinite(a))
                       | jnp.any(~jnp.isfinite(k))
                       | jnp.any(~jnp.isfinite(b)))
                x = _reject(bad, x, xn)
                s = _reject(bad, s, sn_)
                c = _reject(bad, c, cn)
                k = jnp.where(bad, kold, k)
                status, bestk, stall = _guard_update(
                    status, bestk, stall, bad, k, done, stall_n)
            else:
                x, s, c = xn, sn_, cn
        with _trace.span("solver.cost"):
            iiter = iiter + 1
            sn = jnp.asarray(s.norm())
            cost = lax.dynamic_update_index_in_dim(cost, sn, iiter, 0)
            r2 = jnp.sqrt(sn ** 2 + damp2 * _rdot(x, x))
            cost1 = lax.dynamic_update_index_in_dim(cost1, r2, iiter, 0)
        # no-op unless telemetry is enabled (see _make_cg_body note)
        telemetry.iteration("cgls", iiter, resid=sn, k=k, alpha=a)
        if guards:
            return (x, s, c, k, iiter, cost, cost1, status, bestk, stall)
        if carry_status:
            return (x, s, c, k, iiter, cost, cost1, status)
        return (x, s, c, k, iiter, cost, cost1)

    if fresh:
        return body_fresh
    return body_normal if normal else body_classic


def _cgls_setup(Op, y: Vector, x0: Vector, damp, damp2, *, niter: int,
                normal: bool, M=None):
    """Shared CGLS prologue: residuals, first direction, recurrence
    norm, machine-precision floor and the cost buffers — used by the
    single-shot fused loops here and the segmented driver
    (solvers/segmented.py), which must seed the exact same carry.
    Where the fresh-residual body follows (:func:`_fresh`), ``s = y −
    Op x`` and ``Opᴴ s`` come from one read of the operator too:
    ``fresh_normal_matvec(x, y)``, its adjoint at a unit step; the
    carry's head is then ``(x, s, c, kold)``: no ``r`` to carry."""
    x = x0  # donated: carry aliases the caller's buffer (see _DONATE_X0)
    fresh = _fresh(Op, normal)
    if fresh:
        q, adjoint = Op.fresh_normal_matvec(x, y)
        with _trace.span("solver.setup"):
            s = y - q
        rq = adjoint(jnp.ones((), np.dtype(_vdtype(x0))))
    else:
        s = Op.matvec(x)
        with _trace.span("solver.setup"):
            s = y - s
        rq = Op.rmatvec(s)
    with _trace.span("solver.setup"):
        # ref's un-squared setup damp (see module doc) seeds only the
        # first direction, as in the classic path
        rq = rq - x * damp
    z = _precond_apply(M, rq, _vdtype(x0))
    c = z
    if not normal:
        q = Op.matvec(c)
    with _trace.span("solver.setup"):
        kold = _rdot(rq, z)
        floors = _mp_floor(kold)
        if normal and not fresh:
            # the recurrence tracks the true gradient r = Opᴴs − damp²x,
            # so it must start from the damp²-form, not the quirked one
            r = rq + x * (damp - damp2)
        sn0 = jnp.asarray(s.norm())
        cost0 = jnp.zeros((niter + 1,) + jnp.shape(sn0), dtype=sn0.dtype)
        cost0 = lax.dynamic_update_index_in_dim(cost0, sn0, 0, 0)
        cost1_0 = lax.dynamic_update_index_in_dim(
            jnp.zeros_like(cost0),
            jnp.sqrt(sn0 ** 2 + damp2 * _rdot(x, x)), 0, 0)
    if fresh:
        return (x, s, c, kold), floors, cost0, cost1_0
    if normal:
        return (x, s, r, c, kold), floors, cost0, cost1_0
    return (x, s, c, q, kold), floors, cost0, cost1_0


def _cgls_fused_any(Op, y: Vector, x0: Vector, damp, tol, *, niter: int,
                    normal: bool, guards: bool, M=None, stall_n: int = 0,
                    fault=None):
    """The fused CGLS program, both sweep schedules, guards on and off:
    :func:`_cgls_setup`, then :func:`_make_cgls_body` under
    ``lax.while_loop``. Flat vectors in, a flat ``x`` out, as
    ``pmt.cgls`` promises.

    Inside, the loop holds each of its four vectors in the shape the
    operator declares for its side (:func:`_while_carried`:
    ``(192, 1024, 1024)`` cubes in the stacked post-stack system,
    ``(1023, 65536)`` under ``MPIMDC``, flat wherever
    :func:`_carry_shape` says so): ONE reshape of ``head``'s vectors
    after the set-up — on a TPU a relayout of each, once a solve — and
    ONE flatten of ``x`` at the exit, in place of a relayout copy at
    every operator's edge every iteration. The set-up, the body and
    every operator's apply are the flat ones, unchanged (so
    ``solvers/segmented.py``, which seeds the same carry and runs the
    same body, still carries flat); only the order in which a
    reduction's partial sums meet can differ from the flat program's.
    A fresh ``x0`` is donated as before (see ``_DONATE_X0``); a
    caller's is copied into the carry at entry (``_run_cgls_fused``),
    which the entry's reshape now is."""
    damp2 = damp ** 2
    xdt = _vdtype(x0)
    fresh = _fresh(Op, normal)
    head, floors, cost0, cost1_0 = _cgls_setup(Op, y, x0, damp, damp2,
                                               niter=niter, normal=normal,
                                               M=M)
    body = _make_cgls_body(Op, xdt, damp2, floors, M=M, normal=normal,
                           fresh=fresh, guards=guards, stall_n=stall_n,
                           fault=fault)
    # head's vectors: (x, s, r, c) one-sweep, (x, s, c) fresh, (x, s, c,
    # q) classic; kold follows them
    sides = (("dims", "dimsd", "dims") if fresh
             else ("dims", "dimsd", "dims", "dims") if normal
             else ("dims", "dimsd", "dims", "dimsd"))
    n = len(sides)
    if guards:
        from ..resilience import status as _rstatus
        kold0 = head[n]
        state = head + (jnp.asarray(0), cost0, cost1_0,
                        _i32(_rstatus.RUNNING), jnp.max(kold0), _i32(0))

        def cond(state):
            return ((state[n + 1] < niter) & (jnp.max(state[n]) > tol)
                    & (state[n + 4] == _rstatus.RUNNING))

        out = _while_carried("cgls", Op, cond, body, state, sides)
        x, kold, iiter, cost, cost1, status = (out[0], out[n], out[n + 1],
                                               out[n + 2], out[n + 3],
                                               out[n + 4])
        return (x, iiter, cost, cost1, kold,
                _resolve_status(status, kold, tol))

    def cond(state):
        return (state[n + 1] < niter) & (jnp.max(state[n]) > tol)

    state = head + (jnp.asarray(0), cost0, cost1_0)
    out = _while_carried("cgls", Op, cond, body, state, sides)
    return out[0], out[n + 1], out[n + 2], out[n + 3], out[n]


def _cgls_fused(Op, y: Vector, x0: Vector, damp, tol, *, niter: int,
                guards: bool = False, M=None, stall_n: int = 0,
                fault=None):
    return _cgls_fused_any(Op, y, x0, damp, tol, niter=niter,
                           normal=False, guards=guards, M=M,
                           stall_n=stall_n, fault=fault)


def _cgls_fused_normal(Op, y: Vector, x0: Vector, damp, tol, *,
                       niter: int, guards: bool = False, M=None,
                       stall_n: int = 0, fault=None):
    """CGLS with one operator memory sweep per iteration: the step uses
    ``(u, q) = Op.normal_matvec(c)`` (``u = OpᴴOp c`` computed in the
    same pass that yields ``q = Op c``) and the gradient recurrence
    ``r ← r − a (u + damp² c)``, which is algebraically identical to the
    textbook ``r = Opᴴ s − damp² x`` (s-update substituted). Halves HBM
    traffic on memory-bound matvecs; enabled when
    ``Op.has_fused_normal``. On an operator that offers
    ``fresh_normal_matvec`` (``has_fresh_normal``: ``MPIMDC``'s chain)
    the same sweep also gives ``Opᴴ s``, and the body makes ``r`` anew
    each iteration instead (:func:`_make_cgls_body`'s ``fresh``)."""
    return _cgls_fused_any(Op, y, x0, damp, tol, niter=niter,
                           normal=True, guards=guards, M=M,
                           stall_n=stall_n, fault=fault)


# Bounded LRU of compiled fused solvers. The operator itself is stored
# alongside the jitted fn: keeping it alive pins its id(), making the
# id-based key collision-free, and eviction drops both the executable
# and the operator's device buffers.
#
# Two documented consequences (round-1 VERDICT weak #9):
# - up to PYLOPS_MPI_TPU_FUSED_CACHE (default 32) operators stay alive
#   through the cache, holding their device buffers — call
#   clear_fused_cache() in long-lived sessions that churn operators;
# - an operator evicted and then reused recompiles silently (first
#   solve pays compile time again). Raise the env cap when iterating
#   over more than 32 distinct (operator, niter, shape) combinations.
import os
from collections import OrderedDict

_FUSED_CACHE: "OrderedDict" = OrderedDict()
try:
    _FUSED_CACHE_MAX = max(
        1, int(os.environ.get("PYLOPS_MPI_TPU_FUSED_CACHE", "32")))
except ValueError:  # malformed env var must not break import
    _FUSED_CACHE_MAX = 32


def clear_fused_cache() -> None:
    """Drop every cached fused-solver executable and the operator
    references (and device buffers) they pin."""
    _FUSED_CACHE.clear()


def _get_fused(Op, key, make_builder, donate_argnums=(), keepalive=None,
               aot_eligible=False):
    """Compile (and cache) the fused loop for ``Op``.
    ``make_builder(op)`` must return the loop with that operator bound;
    the returned fn is called with POSITIONAL runtime operands (the
    builder calling convention above). ``donate_argnums`` are indices
    into those operands whose buffers the program may consume in place
    (the while_loop carry starts in the donated buffer instead of a
    program-entry copy) — applied only when the precision layer's
    donation gate is on (``PYLOPS_MPI_TPU_DONATE``), and folded into
    the cache key so flipping the gate retraces rather than reusing an
    executable with the wrong aliasing contract.

    Registered operator classes (``linearoperator.OP_ARRAY_PYTREES``)
    enter the jitted program as a pytree ARGUMENT — their device
    buffers are traced, not closed over, which multi-process JAX
    requires for arrays spanning non-addressable devices (exercised by
    tests/multihost_worker.py). Unregistered operators keep the
    closure form.

    ``keepalive`` pins any extra object whose ``id()`` participates in
    ``key`` (the preconditioner ``M``) for the life of the cache entry,
    so a freed-then-reallocated object can never alias a stale key.

    ``aot_eligible=True`` (set only by call sites whose key carries no
    process-local ids past element 0 — unpreconditioned, no armed
    fault spec) routes the jit-argument branch through the AOT
    executable bank (``pylops_mpi_tpu/aot/``) when
    ``PYLOPS_MPI_TPU_AOT`` arms it: the program is lowered+compiled
    explicitly, serialized to the bank, and on the next process start
    loaded in milliseconds instead of recompiled. With the tier off
    (the default) this parameter contributes NOTHING — same jit, same
    keys, bit-identical HLO (tests/test_aot.py pins it)."""
    from ..linearoperator import operator_is_jit_arg
    from ..ops._precision import donation_enabled
    donate = tuple(donate_argnums) if donation_enabled() else ()
    # telemetry state is compile-relevant: a program traced with the
    # in-loop debug callbacks embedded must never be reused when the
    # gate is off (and vice versa) — same pattern as the donation gate.
    # So is the reduce_stall latency seam (it traces a scalar chain
    # into every reduction); disarmed it contributes NOTHING, keeping
    # pre-seam keys byte-identical.
    from ..parallel.collectives import stall_signature
    key = key + (donate, telemetry.telemetry_signature()) \
        + stall_signature()
    entry = _FUSED_CACHE.get(key)
    if entry is None:
        if operator_is_jit_arg(Op):
            jfn = jax.jit(lambda op, *a: make_builder(op)(*a),
                          donate_argnums=tuple(i + 1 for i in donate))
            fn = None
            if aot_eligible:
                from .. import aot as _aot
                fn = _aot.maybe_aot_fused(jfn, Op, key)
            if fn is None:
                def fn(*a, _jfn=jfn, _op=Op):
                    return _jfn(_op, *a)
        else:
            fn = jax.jit(make_builder(Op), donate_argnums=donate)
        entry = (fn, Op, keepalive)
        _FUSED_CACHE[key] = entry
        if len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
            _FUSED_CACHE.popitem(last=False)
    else:
        _FUSED_CACHE.move_to_end(key)
    return entry[0]


def _donate_copy(v: Vector) -> Vector:
    """Fresh-buffer copy of a caller-owned vector so the fused entry
    can donate it: donation consumes the argument's buffer, and the
    public wrappers must not invalidate a vector the caller may reuse.
    One eager vector copy per solve — negligible against the solve,
    and the program-entry copy it replaces was the same bytes."""
    from ..ops._precision import donation_enabled
    return v.copy() if donation_enabled() else v


def _run_cg_fused(Op, y: Vector, x0: Vector, x0_owned: bool, niter: int,
                  tol, guards: bool, M=None):
    """Compile-cache-and-run the fused CG loop. Returns ``(x, iiter,
    cost, status_code)`` — ``status_code`` is ``None`` on the unguarded
    path (whose traced program is bit-identical to the pre-guard
    build; the guard carries only exist under ``guards=True``).
    ``M=None`` leaves the cache key byte-identical to the pre-seam
    layout (``_mkey`` contributes nothing), so unpreconditioned solves
    reuse existing entries.

    ``PYLOPS_MPI_TPU_CA`` routes here: any mode but ``off`` dispatches
    to the communication-avoiding tier (solvers/ca.py) under its own
    cache keys; ``off`` takes the classic path below untouched — same
    keys, same trace, bit-identical HLO (tests/test_ca.py)."""
    from . import ca as _ca
    _ca_mode = _ca.resolve_mode(Op, "cg")
    if _ca_mode != "off":
        return _ca.run_cg_fused(Op, y, x0, x0_owned, niter, tol,
                                guards, M=M, mode=_ca_mode)
    if guards:
        from ..resilience import faults as _faults, status as _rstatus
        spec = _faults.consume()
        stall_n = _rstatus.stall_window()
        fn = _get_fused(Op, (id(Op), "cg", niter, _vkey(y), _vkey(x0),
                             _rstatus.guards_signature(True),
                             _faults.fault_signature(spec)) + _mkey(M),
                        lambda op: partial(_cg_fused, op, niter=niter,
                                           guards=True, M=M,
                                           stall_n=stall_n, fault=spec),
                        donate_argnums=_DONATE_X0, keepalive=M,
                        aot_eligible=(M is None and spec is None))
        x, iiter, cost, status = fn(
            y, x0 if x0_owned else _donate_copy(x0), tol)
        iiter, code = int(iiter), int(status)
        _rstatus.record("cg", code, iiter)
        _metrics.inc("solver.cg.solves")
        _metrics.inc("solver.cg.iterations", iiter)
        return x, iiter, np.asarray(cost)[:iiter + 1], code
    fn = _get_fused(Op, (id(Op), "cg", niter, _vkey(y),
                         _vkey(x0)) + _mkey(M),
                    lambda op: partial(_cg_fused, op, niter=niter, M=M),
                    donate_argnums=_DONATE_X0, keepalive=M,
                    aot_eligible=(M is None))
    x, iiter, cost = fn(y, x0 if x0_owned else _donate_copy(x0), tol)
    iiter = int(iiter)
    # host-side, AFTER the fused loop returned: metrics never add an
    # in-loop callback (the fleet-obs HLO pin)
    _metrics.inc("solver.cg.solves")
    _metrics.inc("solver.cg.iterations", iiter)
    return x, iiter, np.asarray(cost)[:iiter + 1], None


def cg(Op, y: Vector, x0: Optional[Vector] = None, niter: int = 10,
       tol: float = 1e-4, show: bool = False, itershow=(10, 10, 10),
       callback: Optional[Callable] = None, fused: Optional[bool] = None,
       guards: Optional[bool] = None,
       M=None) -> Tuple[Vector, int, np.ndarray]:
    """Functional CG (ref ``optimization/basic.py:13-70``). With no
    callback/show, runs the fused on-device loop. ``guards`` resolves
    against ``PYLOPS_MPI_TPU_GUARDS`` (resilience/status.py): guarded
    fused solves can exit early on breakdown/stagnation — the return
    signature is unchanged, the status word lands in
    ``resilience.status.last_status("cg")``.

    ``M`` is an optional preconditioner (an ``MPILinearOperator``
    approximating ``Op⁻¹``, SPD) applied to the residual inside the
    fused while_loop — see docs/preconditioning.md. Fused path only.

    Under ``PYLOPS_MPI_TPU_AUTODIFF=on``, traced inputs (calls inside
    ``jax.jit``/``jax.grad``) reroute to the implicit-diff rule
    (autodiff/implicit.py) instead of failing on host conversions —
    fused path only, guards excluded; with the knob off (default) this
    check is one host-side env read and the traced/lowered programs
    are bit-identical (tests/test_autodiff.py pins it)."""
    from ..utils import deps as _deps
    if _deps.autodiff_enabled():
        from ..autodiff import implicit as _autodiff
        if _autodiff.should_intercept(Op, y, x0):
            if callback is not None or show or fused is False:
                raise ValueError(
                    "traced cg() (PYLOPS_MPI_TPU_AUTODIFF=on) supports "
                    "only the fused path: callback/show/fused=False "
                    "need host synchronization inside the trace")
            return _autodiff.entry_cg(Op, y, x0, niter, tol, M)
    x0_owned = x0 is None  # freshly built → donate without a copy
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    use_fused = fused if fused is not None else (callback is None and not show)
    if use_fused and (callback is not None or show):
        raise ValueError("fused=True cannot honor callback/show; use "
                         "fused=False for per-iteration hooks")
    if M is not None and not use_fused:
        raise ValueError("M= (preconditioning) requires the fused path; "
                         "drop callback/show or pass fused=True")
    from ..resilience.status import guards_enabled
    use_guards = use_fused and guards_enabled(guards)
    with _trace.span("solver.cg", cat="solver", op=type(Op).__name__,
                     shape=Op.shape, dtype=_vdtype(x0), niter=niter,
                     tol=tol, fused=use_fused, guards=use_guards,
                     carry=_carry_tag(Op, x0, None, use_fused),
                     telemetry=telemetry.telemetry_enabled()), \
            _metrics.timer("solver.cg"):
        if use_fused:
            x, iiter, cost, _ = _run_cg_fused(Op, y, x0, x0_owned,
                                              niter, tol, use_guards,
                                              M=M)
            return x, iiter, cost
        solver = CG(Op)
        solver._callback_wrap(callback)
        x, iiter, cost = solver.solve(y, x0, niter=niter, tol=tol,
                                      show=show, itershow=itershow)
        return x, iiter, cost


def cg_guarded(Op, y: Vector, x0: Optional[Vector] = None,
               niter: int = 10, tol: float = 1e-4, M=None):
    """Guarded fused CG with an explicit status word: returns
    ``(x, iiter, cost, status_code)`` where the code is one of
    ``resilience.status.{CONVERGED, MAXITER, BREAKDOWN, STAGNATION}``.
    On breakdown ``x`` is the last finite iterate — the restart seed
    for :func:`pylops_mpi_tpu.resilience.resilient_solve`."""
    x0_owned = x0 is None
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    with _trace.span("solver.cg", cat="solver", op=type(Op).__name__,
                     shape=Op.shape, dtype=_vdtype(x0), niter=niter,
                     tol=tol, fused=True, guards=True,
                     carry=_carry_tag(Op, x0, None, True),
                     telemetry=telemetry.telemetry_enabled()), \
            _metrics.timer("solver.cg"):
        return _run_cg_fused(Op, y, x0, x0_owned, niter, tol, True, M=M)


def _resolve_normal(Op, x0: Vector, normal: Optional[bool],
                    use_fused: bool = True) -> bool:
    """The one rule for CGLS's sweep schedule (``cgls``,
    ``cgls_guarded``, ``resilient_solve``, ``block_cgls``): a caller's
    ``True``/``False`` wins; ``None`` asks the operator whether its
    ``normal_matvec`` would run a compiled one-sweep kernel that pays
    for the model vector (or ``(rows, K)`` block of columns) at hand
    (``MPILinearOperator.prefers_fused_normal``) — never off the fused
    path, which has no one-sweep body. Which one-sweep body runs is the
    operator's to say too (:func:`_fresh`)."""
    if normal is not None:
        return bool(normal)
    ask = getattr(Op, "prefers_fused_normal", None)
    return bool(use_fused and ask is not None and ask(x0))


def _fresh(Op, normal: bool) -> bool:
    """Whether a one-sweep CGLS on ``Op`` runs the fresh-residual body
    (:func:`_make_cgls_body`): decided by what the operator is — one
    that offers ``fresh_normal_matvec`` (``has_fresh_normal``) — and by
    nothing else."""
    return bool(normal and getattr(Op, "has_fresh_normal", False))


def _count_cgls_solve(iiter: int, use_normal: bool,
                      solver: str = "cgls", fresh: bool = False) -> None:
    _metrics.inc(f"solver.{solver}.solves")
    _metrics.inc(f"solver.{solver}.iterations", iiter)
    if use_normal:
        _metrics.inc(f"solver.{solver}.one_sweep")
    if fresh:
        _metrics.inc(f"solver.{solver}.fresh_residual")


def _run_cgls_fused(Op, y: Vector, x0: Vector, x0_owned: bool,
                    niter: int, damp, tol, use_normal: bool,
                    guards: bool, M=None):
    """Compile-cache-and-run the fused CGLS loop; see
    :func:`_run_cg_fused` for the guard/status contract (including the
    ``M=None`` cache-key neutrality). Returns
    ``(x, iiter, cost, cost1, kold, status_code_or_None, kmax)``,
    ``kmax`` being ``max(kold)`` on the host. Non-``off``
    ``PYLOPS_MPI_TPU_CA`` modes dispatch to solvers/ca.py (whose CGLS
    cost lanes carry normal-residual norms — docs/ca.md).

    A solve turns to the host as seldom as it can. The small results
    (``iiter``, the two cost histories, ``kold``,
    the status word) come to the host in ONE round (``jax.device_get``
    starts every copy before it waits for the first), and ``damp`` and
    ``tol`` are device scalars made once a value
    (:func:`_scalar_operand`). Before, a solve was two scalar
    transfers, the program, ``int(iiter)``, two ``np.asarray`` and
    ``float(jnp.max(kold))`` (a second program): every one a hand-over
    between threads whose cost is the host's to decide. On a one-chip
    machine, whose cores are shared, whole processes ran each such
    step 2-3 x slower than others (``flagship_n4096.solve_k1``: 371.0
    and 377.6 ms a solve on the same code, the device's 363.3 ms the
    same in both; with this and the one-dispatch zeros of a fresh
    ``DistributedArray``, 368.0 and 371.6; PERF.md section 6, PR 33)."""
    from . import ca as _ca
    _ca_mode = _ca.resolve_mode(Op, "cgls")
    if _ca_mode != "off":
        # the CA engine has no fresh-residual body: on an operator that
        # offers one, its one-sweep product is not what CA would run, so
        # the solve is classic there and counted so (docs/ca.md)
        out = _ca.run_cgls_fused(Op, y, x0, x0_owned, niter, damp, tol,
                                 use_normal and not _fresh(Op, use_normal),
                                 guards, M=M, mode=_ca_mode)
        return out + (float(jnp.max(out[4])),)
    # The wrapper's two host phases tile it, inside the caller's
    # ``pmt.solver.<name>`` span: ``launch`` until the fused program's
    # asynchronous call has returned, ``collect`` until the small
    # results are on the host. Two annotations a solve, no wait of
    # their own.
    with _trace.span("solver.launch", cat="solver", solver="cgls"):
        builder = _cgls_fused_normal if use_normal else _cgls_fused
        # A caller's ``x0`` is NOT donated here: the program copies it
        # into the carry at entry — the same bytes as the eager
        # ``_donate_copy`` the other solvers make, without a device op
        # of the vector's size dispatched at the head of the solver's
        # span. On the chip's profiler the device's clock runs ~1.2 ms
        # ahead of the host's, so that eager copy of an 805 MB ``x0``
        # began 0.54-0.99 ms BEFORE the span that dispatched it in four
        # traces, against the 1 ms the benchmark's clock check allows
        # (PERF.md section 6, PR 32): the yardstick is not this PR's to
        # mend, and a violation silences five per-layer metrics of a
        # cell. ``donate`` is part of the cache key (``_get_fused``), so
        # the two entries never mix. To go back to ``_donate_copy`` with
        # the check's offset (PERF.md section 7).
        donate = _DONATE_X0 if x0_owned else ()
        key = (id(Op), "cgls", use_normal, niter, _vkey(y), _vkey(x0))
        args = (y, x0, _scalar_operand(damp, y), _scalar_operand(tol, y))
        if guards:
            from ..resilience import faults as _faults, status as _rstatus
            spec = _faults.consume()
            stall_n = _rstatus.stall_window()
            fn = _get_fused(Op, key + (_rstatus.guards_signature(True),
                                       _faults.fault_signature(spec))
                            + _mkey(M),
                            lambda op: partial(builder, op, niter=niter,
                                               guards=True, M=M,
                                               stall_n=stall_n,
                                               fault=spec),
                            donate_argnums=donate, keepalive=M,
                            aot_eligible=(M is None and spec is None))
        else:
            fn = _get_fused(Op, key + _mkey(M),
                            lambda op: partial(builder, op, niter=niter,
                                               M=M),
                            donate_argnums=donate, keepalive=M,
                            aot_eligible=(M is None))
        x, iiter, cost, cost1, kold, *status = fn(*args)
    with _trace.span("solver.collect", cat="solver", solver="cgls"):
        iiter, cost, cost1, kmax, *status = jax.device_get(
            (iiter, cost, cost1, kold, *status))
        iiter, code = int(iiter), None
        if guards:
            code = int(status[0])
            _rstatus.record("cgls", code, iiter)
        _count_cgls_solve(iiter, use_normal,
                          fresh=_fresh(Op, use_normal))
        return (x, iiter, cost[:iiter + 1], cost1[:iiter + 1], kold, code,
                float(np.max(kmax)))


_SCALAR_OPERANDS: "OrderedDict" = OrderedDict()


def _scalar_operand(v, like: Optional[Vector] = None):
    """A Python ``float``/``int`` operand of a fused solve as a device
    scalar made once a value (weakly typed, as ``jax.jit`` would make
    it: the same program): a solve then starts no host-to-device
    transfer of its own for ``damp`` and ``tol``. Anything else — an
    array, a NumPy scalar, a tracer — passes as it is; so does every
    operand of a solve whose vectors (``like``) lie on more than one
    device or process, where a scalar made here would lie on one and
    be copied to the others every call."""
    if type(v) not in (float, int) or jax.process_count() > 1:
        return v
    if like is not None:
        first = like.distarrays[0] if isinstance(
            like, StackedDistributedArray) else like
        if int(first.mesh.devices.size) != 1:
            return v
    k = (type(v), v, jax.config.jax_enable_x64)
    if k not in _SCALAR_OPERANDS:
        _SCALAR_OPERANDS[k] = jnp.asarray(v)
        if len(_SCALAR_OPERANDS) > 64:
            _SCALAR_OPERANDS.popitem(last=False)
    return _SCALAR_OPERANDS[k]


def cgls(Op, y: Vector, x0: Optional[Vector] = None, niter: int = 10,
         damp: float = 0.0, tol: float = 1e-4, show: bool = False,
         itershow=(10, 10, 10), callback: Optional[Callable] = None,
         fused: Optional[bool] = None, normal: Optional[bool] = None,
         guards: Optional[bool] = None, M=None):
    """Functional CGLS (ref ``optimization/basic.py:73-148``).

    ``normal`` picks the sweep schedule of the fused loop. ``True`` is
    the one-sweep normal-equations iteration (``_cgls_fused_normal``:
    ``(u, q) = Op.normal_matvec(c)`` and the gradient recurrence
    ``r ← r − a(u + damp²c)``), ``False`` the classic matvec + rmatvec
    pair. ``None`` (default) asks the operator
    (``Op.prefers_fused_normal(x0)``): one sweep only where
    ``normal_matvec`` would run a compiled one-sweep kernel that beats
    two sweeps for this vector — today a batched ``MPIBlockDiag`` of
    real blocks on a 1-D mesh, on a TPU (Mosaic), a real model of the
    blocks' accumulation dtype, a row tile the chip has shown fast; and
    ``MPIMDC``'s chain, whose plane-pair kernel ``pmt_normal_planes``
    gives ``Op c`` and ``Opᴴ s`` from one read of the planes
    (``ops/mdc.py``), for one vector on a TPU; everything else, and
    every operator on the CPU, compiles the classic program. An
    operator that offers ``fresh_normal_matvec`` (``MPIMDC``) runs the
    one-sweep schedule with the normal residual made anew each
    iteration, ``r = Opᴴ s − damp² x``, whose error stays at the
    classic schedule's (the recurrence below drifts to 2e-5 on that
    operator; :func:`_make_cgls_body`). The recurrence carries rounding
    of its own: in f32 its error to the true model stayed within 1.04 ×
    the classic
    schedule's at cond 3 / 100 / 1000 over 30–400 iterations on evenly
    spaced spectra; sitting on the f32 floor of a log-spaced one (cond
    100, 400 iterations) single seeds scatter 0.85–1.27 × to both
    sides (``tests/test_cgls_normal_default.py`` holds both).
    ``tol``, ``niter``, ``damp``, ``cost`` (the true residual norm —
    ``s`` is carried) and ``istop`` mean the same on both. The
    segmented solver (solvers/segmented.py) always runs the classic
    schedule.
    ``guards`` resolves against ``PYLOPS_MPI_TPU_GUARDS`` (see
    :func:`cg`); the status word lands in
    ``resilience.status.last_status("cgls")``.

    ``M`` is an optional preconditioner for the NORMAL system — an SPD
    ``MPILinearOperator`` approximating ``(OpᴴOp + damp²I)⁻¹``, applied
    to the normal residual ``Opᴴ s − damp² x`` inside the fused loop
    (docs/preconditioning.md). Fused path only.

    ``PYLOPS_MPI_TPU_AUTODIFF=on`` reroutes traced inputs to the
    implicit-diff rule — see :func:`cg` (same fused-only restriction;
    ``normal=True`` is a forward-schedule choice the fixed-point rule
    does not need, so the traced path always runs the classic
    two-sweep schedule)."""
    from ..utils import deps as _deps
    if _deps.autodiff_enabled():
        from ..autodiff import implicit as _autodiff
        if _autodiff.should_intercept(Op, y, x0):
            if callback is not None or show or fused is False:
                raise ValueError(
                    "traced cgls() (PYLOPS_MPI_TPU_AUTODIFF=on) "
                    "supports only the fused path: callback/show/"
                    "fused=False need host synchronization inside the "
                    "trace")
            return _autodiff.entry_cgls(Op, y, x0, niter, damp, tol, M)
    x0_owned = x0 is None  # freshly built → donate without a copy
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    use_fused = fused if fused is not None else (callback is None and not show)
    if use_fused and (callback is not None or show):
        raise ValueError("fused=True cannot honor callback/show; use "
                         "fused=False for per-iteration hooks")
    if M is not None and not use_fused:
        raise ValueError("M= (preconditioning) requires the fused path; "
                         "drop callback/show or pass fused=True")
    use_normal = _resolve_normal(Op, x0, normal, use_fused)
    if use_normal and not use_fused:
        raise ValueError("normal=True requires the fused path; drop "
                         "callback/show or pass fused=True")
    from ..resilience.status import guards_enabled
    use_guards = use_fused and guards_enabled(guards)
    with _trace.span("solver.cgls", cat="solver", op=type(Op).__name__,
                     shape=Op.shape, dtype=_vdtype(x0), niter=niter,
                     damp=damp, tol=tol, fused=use_fused,
                     normal=use_normal, guards=use_guards,
                     carry=_carry_tag(Op, x0, y, use_fused),
                     telemetry=telemetry.telemetry_enabled()), \
            _metrics.timer("solver.cgls"):
        if use_fused:
            x, iiter, cost, cost1, kold, _, kmax = _run_cgls_fused(
                Op, y, x0, x0_owned, niter, damp, tol, use_normal,
                use_guards, M=M)
            istop = 1 if kmax < tol else 2
            return x, istop, iiter, kold, cost1[-1], cost
        solver = CGLS(Op)
        solver._callback_wrap(callback)
        return solver.solve(y, x0, niter=niter, damp=damp, tol=tol,
                            show=show, itershow=itershow)


def cgls_guarded(Op, y: Vector, x0: Optional[Vector] = None,
                 niter: int = 10, damp: float = 0.0, tol: float = 1e-4,
                 normal: Optional[bool] = None, M=None):
    """Guarded fused CGLS with an explicit status word: returns
    ``(x, iiter, cost, cost1, kold, status_code)``; see
    :func:`cg_guarded` for the status contract and :func:`cgls` for
    ``normal``."""
    x0_owned = x0 is None
    if x0 is None:
        x0 = _zero_like_model(Op, y)
    use_normal = _resolve_normal(Op, x0, normal)
    with _trace.span("solver.cgls", cat="solver", op=type(Op).__name__,
                     shape=Op.shape, dtype=_vdtype(x0), niter=niter,
                     damp=damp, tol=tol, fused=True,
                     normal=use_normal, guards=True,
                     carry=_carry_tag(Op, x0, y, True),
                     telemetry=telemetry.telemetry_enabled()), \
            _metrics.timer("solver.cgls"):
        return _run_cgls_fused(Op, y, x0, x0_owned, niter, damp, tol,
                               use_normal, True, M=M)[:6]


def _vkey(v: Vector):
    if isinstance(v, StackedDistributedArray):
        return tuple(_vkey(d) for d in v.distarrays)
    return (v.global_shape, v.partition, v.axis, v.mask, str(v.dtype))


def _zero_like_model(Op, y: Vector) -> Vector:
    """Build a zero initial model matching ``Op``'s input space."""
    if hasattr(Op, "model_template"):
        return Op.model_template()
    if isinstance(y, DistributedArray):
        return DistributedArray(global_shape=Op.shape[1], mesh=y.mesh,
                                partition=y.partition, dtype=y.dtype)
    raise ValueError("x0 required for stacked model spaces")
