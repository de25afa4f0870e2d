"""Block-Krylov solvers and the vmap-over-parameters batched engine.

Serving-scale workloads arrive as MANY same-shape inverse problems —
shot gathers, deconvolution panels, tomography slices — and solving
them one RHS at a time leaves the amortization on the table twice:
every solve re-walks the operator's memory (the matvec is bandwidth
bound, so K columns through one GEMM cost barely more than one) and
every distinct problem recompiles or re-tunes. Two batching axes fix
the two wastes:

- **block solvers** (:func:`block_cg`, :func:`block_cgls`): ONE
  operator, K RHS columns carried through one fused ``lax.while_loop``.
  The data/model vectors are 2-D ``DistributedArray``\\ s ``(n, K)``
  (rows sharded, trailing column axis local); every operator apply
  moves all K columns per step (the widened-GEMM paths in
  MatrixMult/BlockDiag/stacks/Fredholm1), and the recurrence scalars
  become ``(K,)`` vectors via :meth:`DistributedArray.col_dot`.
  Columns converge independently: a per-column ``done`` mask freezes
  finished columns in-loop (zero step + zero momentum — the same
  select trick as the machine-precision freeze in ``solvers/basic``),
  and with guards on each column carries its own status word, so a
  poisoned column breaks down alone while its siblings keep iterating.
- **vmap over operator parameters** (:func:`batched_solve`): B
  operators from one factory, differing only in tensor data (e.g. MDC
  kernels), stacked leaf-wise and pushed through ``jax.vmap`` of the
  single-RHS fused loop — one compile for the whole family.

``K=1`` block solves route to the EXACT single-RHS fused program
(same ``_get_fused`` cache entry → bit-identical HLO, pinned by
tests/test_block_solver.py). Buffer donation covers the block carries
(``x0`` is ``(n, K)`` and donated like the 1-D case), and telemetry
records per-column residual vectors (``diagnostics/telemetry`` stores
size>1 samples as lists) with the same zero-host-callback-off
guarantee. See docs/batching.md for when each axis wins.
"""

from __future__ import annotations

import os
from collections import OrderedDict, namedtuple
from functools import partial
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..distributedarray import DistributedArray
from ..diagnostics import metrics as _metrics
from ..diagnostics import telemetry, trace as _trace
from .basic import (_DONATE_X0, _count_cgls_solve, _donate_copy, _get_fused,
                    _i32, _mkey, _mp_floor, _precond_apply,
                    _precond_signature, _reject, _resolve_normal,
                    _step_scalar, _vdtype, _vkey)

__all__ = ["block_cg", "block_cgls", "block_cg_segmented",
           "batched_solve", "BatchedResult", "batched_cache_info"]


def _bdot(u: DistributedArray, v: DistributedArray):
    """Per-column recurrence dot at the policy reduction dtype — the
    ``(K,)`` twin of ``solvers.basic._rdot`` (including its
    ``reduce_stall`` latency seam: no-op unless armed)."""
    from ..ops._precision import reduction_dtype
    from ..parallel.collectives import reduce_stall
    return reduce_stall(jnp.abs(u.col_dot(v, vdot=True)).astype(
        reduction_dtype(_vdtype(u))))


def _check_block(Op, y):
    if not (isinstance(y, DistributedArray) and y.ndim == 2):
        raise ValueError(
            "block solvers need a 2-D (rows, columns) DistributedArray "
            f"data vector; got {type(y).__name__} with shape "
            f"{getattr(y, 'global_shape', None)}")
    if y.global_shape[0] != Op.shape[0]:
        raise ValueError(
            f"data rows {y.global_shape[0]} do not match operator rows "
            f"{Op.shape[0]}")


def _squeeze_col(v: DistributedArray) -> DistributedArray:
    """(n, 1) block vector → the 1-D vector the single-RHS programs
    take (K=1 routing)."""
    return DistributedArray._wrap(
        v._arr[..., 0], v, global_shape=(v.global_shape[0],),
        local_shapes=tuple((s[0],) for s in v.local_shapes))


def _expand_col(v: DistributedArray) -> DistributedArray:
    """1-D vector → (n, 1) block vector."""
    return DistributedArray._wrap(
        v._arr[..., None], v, global_shape=v.global_shape + (1,),
        local_shapes=tuple(tuple(s) + (1,) for s in v.local_shapes))


def _zero_block_model(Op, y: DistributedArray) -> DistributedArray:
    K = int(y.global_shape[1])
    return DistributedArray(global_shape=(Op.shape[1], K), mesh=y.mesh,
                            partition=y.partition, axis=0, dtype=y.dtype)


def _status0(K: int):
    from ..resilience import status as _rstatus
    return jnp.full((K,), _rstatus.RUNNING, dtype=jnp.int32)


def _bguard_update(status, bestk, stall, bad, k, done, stall_n: int):
    """Per-column guard-carry step: each column's breakdown/stagnation
    verdict is independent — the column-wise ``where`` of
    ``basic._guard_update``. A verdict is sticky (first one wins) and
    frozen/poisoned columns do not run their stall counter."""
    from ..resilience import status as _rstatus
    improved = (k < bestk) & ~bad
    stall = jnp.where(bad | done, stall,
                      jnp.where(improved, jnp.zeros_like(stall),
                                stall + 1))
    bestk = jnp.where(improved, k, bestk)
    verdict = jnp.where(bad, _i32(_rstatus.BREAKDOWN),
                        jnp.where(stall >= stall_n,
                                  _i32(_rstatus.STAGNATION),
                                  _i32(_rstatus.RUNNING)))
    status = jnp.where(status == _rstatus.RUNNING, verdict, status)
    return status, bestk, stall


def _bresolve(status, kold, tol):
    """Post-loop per-column status resolution (on device)."""
    from ..resilience import status as _rstatus
    return jnp.where(status != _rstatus.RUNNING, status,
                     jnp.where(kold <= tol, _i32(_rstatus.CONVERGED),
                               _i32(_rstatus.MAXITER)))


# ------------------------------------------------------ fused block loops
def _make_block_cg_body(Op, xdt, floors, tol, *, M=None, guards=False,
                        carry_status=False, stall_n=0):
    """Block-CG loop body over ``(x, r, c, kold, iiter, cost
    [, status][, bestk, stall])`` with every recurrence scalar a
    ``(K,)`` vector. Columns freeze individually — at the
    machine-precision floor, at ``tol``, or once their status word
    closes — by zeroing their step/momentum lanes.

    ``M`` preconditions ALL K columns in one apply: ``z = M r`` is one
    block matvec on the ``(n, K)`` residual (operators route 2-D
    inputs through their widened paths or the ``_apply_columns`` vmap
    fallback), and the recurrence becomes ``kold = r·z`` per column.
    The carry layout is unchanged — ``z`` is recomputed each
    iteration, never carried — and ``M=None`` traces the identical
    pre-seam program (``z`` IS ``r``)."""
    from ..resilience import status as _rstatus

    def body(state):
        if guards:
            x, r, c, kold, iiter, cost, status, bestk, stall = state
        elif carry_status:
            x, r, c, kold, iiter, cost, status = state
        else:
            x, r, c, kold, iiter, cost = state
        done = kold <= jnp.maximum(floors, tol)
        if guards or carry_status:
            done = done | (status != _rstatus.RUNNING)
        Opc = Op.matvec(c)
        a = kold / _bdot(c, Opc)
        a = jnp.where(done, jnp.zeros_like(a), a)
        xn = x + c * _step_scalar(a, xdt)
        rn = r - Opc * _step_scalar(a, xdt)
        zn = _precond_apply(M, rn, xdt)
        k = _bdot(rn, zn)
        k = jnp.where(done, kold, k)
        b = jnp.where(done, jnp.zeros_like(k), k / kold)
        cn = zn + c * _step_scalar(b, xdt)
        if guards:
            # per-column verdicts: only the poisoned column's update is
            # rejected (its lane of the (K,) mask), siblings proceed
            bad = (~jnp.isfinite(a)) | (~jnp.isfinite(k)) \
                | (~jnp.isfinite(b))
            x = _reject(bad, x, xn)
            r = _reject(bad, r, rn)
            c = _reject(bad, c, cn)
            k = jnp.where(bad, kold, k)
            status, bestk, stall = _bguard_update(status, bestk, stall,
                                                  bad, k, done, stall_n)
        else:
            x, r, c = xn, rn, cn
        iiter = iiter + 1
        cost = lax.dynamic_update_index_in_dim(cost, jnp.sqrt(k), iiter, 0)
        # per-column residual history; no-op (nothing traced) when
        # telemetry is off — the zero-host-callback pin
        telemetry.iteration("block_cg", iiter, resid=jnp.sqrt(k), k=k,
                            alpha=a)
        if guards:
            return (x, r, c, k, iiter, cost, status, bestk, stall)
        if carry_status:
            return (x, r, c, k, iiter, cost, status)
        return (x, r, c, k, iiter, cost)

    return body


def _block_cg_fused(Op, y, x0, tol, *, niter: int, M=None,
                    guards: bool = False, stall_n: int = 0):
    from ..resilience import status as _rstatus
    xdt = _vdtype(x0)
    x = x0  # donated: the block carry aliases the caller's buffer
    r = y - Op.matvec(x)
    z = _precond_apply(M, r, xdt)
    c = z
    kold = _bdot(r, z)
    floors = _mp_floor(kold)
    cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold),
                      dtype=jnp.asarray(kold).dtype)
    cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold), 0, 0)
    body = _make_block_cg_body(Op, xdt, floors, tol, M=M, guards=guards,
                               stall_n=stall_n)
    if guards:
        K = kold.shape[0]
        state = (x, r, c, kold, jnp.asarray(0), cost0, _status0(K),
                 kold, jnp.zeros((K,), jnp.int32))

        def cond(st):
            return ((st[4] < niter)
                    & jnp.any((st[3] > tol)
                              & (st[6] == _rstatus.RUNNING)))

        x, r, c, kold, iiter, cost, status, _, _ = \
            lax.while_loop(cond, body, state)
        return x, iiter, cost, _bresolve(status, kold, tol)

    def cond(st):
        return (st[4] < niter) & (jnp.max(st[3]) > tol)

    state = (x, r, c, kold, jnp.asarray(0), cost0)
    x, r, c, kold, iiter, cost = lax.while_loop(cond, body, state)
    return x, iiter, cost


def _make_block_cgls_body(Op, xdt, damp2, floors, tol, *, M=None,
                          normal=False, guards=False, carry_status=False,
                          stall_n=0):
    """Block-CGLS loop body — per-column scalars throughout; see
    :func:`_make_block_cg_body`. Classic two-sweep over ``(x, s, c, q,
    kold, iiter, cost, cost1[, status][, bestk, stall])``; ``normal``
    is the one-sweep schedule of ``basic._make_cgls_body`` over ``(x,
    s, r, c, ...)``: ``(u, q) = Op.normal_matvec(c)`` for all K columns
    and the gradient recurrence ``r ← r − a·(u + damp²·c)`` per column.
    ``M`` approximates ``(OpᴴOp + damp²I)⁻¹`` and is applied to the
    normal residual, all K columns at once, in both schedules."""
    from ..resilience import status as _rstatus

    def body_normal(state):
        if guards:
            x, s, r, c, kold, iiter, cost, cost1, status, bestk, stall \
                = state
        elif carry_status:
            x, s, r, c, kold, iiter, cost, cost1, status = state
        else:
            x, s, r, c, kold, iiter, cost, cost1 = state
        done = kold <= jnp.maximum(floors, tol)
        if guards or carry_status:
            done = done | (status != _rstatus.RUNNING)
        u, q = Op.normal_matvec(c)
        with _trace.span("solver.step"):
            a = jnp.abs(kold / (_bdot(q, q) + damp2 * _bdot(c, c)))
            a = jnp.where(done, jnp.zeros_like(a), a)
            xn = x + c * _step_scalar(a, xdt)
            sn_ = s - q * _step_scalar(a, xdt)
            rn = r - (u + c * damp2) * _step_scalar(a, xdt)
        zn = _precond_apply(M, rn, xdt)
        with _trace.span("solver.direction"):
            k = _bdot(rn, zn)
            k = jnp.where(done, kold, k)
            b = jnp.where(done, jnp.zeros_like(k), k / kold)
            cn = zn + c * _step_scalar(b, xdt)
            if guards:
                bad = (~jnp.isfinite(a)) | (~jnp.isfinite(k)) \
                    | (~jnp.isfinite(b))
                x = _reject(bad, x, xn)
                s = _reject(bad, s, sn_)
                r = _reject(bad, r, rn)
                c = _reject(bad, c, cn)
                k = jnp.where(bad, kold, k)
                status, bestk, stall = _bguard_update(
                    status, bestk, stall, bad, k, done, stall_n)
            else:
                x, s, r, c = xn, sn_, rn, cn
        with _trace.span("solver.cost"):
            iiter = iiter + 1
            sn = jnp.sqrt(_bdot(s, s))
            cost = lax.dynamic_update_index_in_dim(cost, sn, iiter, 0)
            r2 = jnp.sqrt(sn ** 2 + damp2 * _bdot(x, x))
            cost1 = lax.dynamic_update_index_in_dim(cost1, r2, iiter, 0)
        telemetry.iteration("block_cgls", iiter, resid=sn, k=k, alpha=a)
        if guards:
            return (x, s, r, c, k, iiter, cost, cost1, status, bestk,
                    stall)
        if carry_status:
            return (x, s, r, c, k, iiter, cost, cost1, status)
        return (x, s, r, c, k, iiter, cost, cost1)

    if normal:
        return body_normal

    def body(state):
        if guards:
            x, s, c, q, kold, iiter, cost, cost1, status, bestk, stall \
                = state
        elif carry_status:
            x, s, c, q, kold, iiter, cost, cost1, status = state
        else:
            x, s, c, q, kold, iiter, cost, cost1 = state
        done = kold <= jnp.maximum(floors, tol)
        if guards or carry_status:
            done = done | (status != _rstatus.RUNNING)
        with _trace.span("solver.step"):
            a = jnp.abs(kold / (_bdot(q, q) + damp2 * _bdot(c, c)))
            a = jnp.where(done, jnp.zeros_like(a), a)
            xn = x + c * _step_scalar(a, xdt)
            sn_ = s - q * _step_scalar(a, xdt)
        r = Op.rmatvec(sn_)
        with _trace.span("solver.direction"):
            r = r - xn * damp2
        z = _precond_apply(M, r, xdt)
        with _trace.span("solver.direction"):
            k = _bdot(r, z)
            k = jnp.where(done, kold, k)
            b = jnp.where(done, jnp.zeros_like(k), k / kold)
            cn = z + c * _step_scalar(b, xdt)
        qn = Op.matvec(cn)
        with _trace.span("solver.direction"):
            if guards:
                bad = (~jnp.isfinite(a)) | (~jnp.isfinite(k)) \
                    | (~jnp.isfinite(b))
                x = _reject(bad, x, xn)
                s = _reject(bad, s, sn_)
                c = _reject(bad, c, cn)
                q = _reject(bad, q, qn)
                k = jnp.where(bad, kold, k)
                status, bestk, stall = _bguard_update(
                    status, bestk, stall, bad, k, done, stall_n)
            else:
                x, s, c, q = xn, sn_, cn, qn
        with _trace.span("solver.cost"):
            iiter = iiter + 1
            sn = jnp.sqrt(_bdot(s, s))
            cost = lax.dynamic_update_index_in_dim(cost, sn, iiter, 0)
            r2 = jnp.sqrt(sn ** 2 + damp2 * _bdot(x, x))
            cost1 = lax.dynamic_update_index_in_dim(cost1, r2, iiter, 0)
        telemetry.iteration("block_cgls", iiter, resid=sn, k=k, alpha=a)
        if guards:
            return (x, s, c, q, k, iiter, cost, cost1, status, bestk,
                    stall)
        if carry_status:
            return (x, s, c, q, k, iiter, cost, cost1, status)
        return (x, s, c, q, k, iiter, cost, cost1)

    return body


def _block_cgls_fused(Op, y, x0, damp, tol, *, niter: int, M=None,
                      normal: bool = False, guards: bool = False,
                      stall_n: int = 0):
    from ..resilience import status as _rstatus
    damp2 = damp ** 2
    xdt = _vdtype(x0)
    x = x0  # donated (see _DONATE_X0)
    s = Op.matvec(x)
    with _trace.span("solver.setup"):
        s = y - s
    rq = Op.rmatvec(s)
    with _trace.span("solver.setup"):
        # the reference's un-squared setup damp quirk (solvers/basic
        # module doc); M seeds the first direction
        rq = rq - x * damp
    z = _precond_apply(M, rq, xdt)
    c = z
    if normal:
        with _trace.span("solver.setup"):
            # the recurrence tracks the true gradient Opᴴs − damp²x:
            # seeded from the damp²-form, as basic._cgls_setup does
            head = (x, s, rq + x * (damp - damp2), c)
    else:
        head = (x, s, c, Op.matvec(c))
    with _trace.span("solver.setup"):
        kold = _bdot(rq, z)
        floors = _mp_floor(kold)
        sn0 = jnp.sqrt(_bdot(s, s))
        cost0 = jnp.zeros((niter + 1,) + jnp.shape(sn0), dtype=sn0.dtype)
        cost0 = lax.dynamic_update_index_in_dim(cost0, sn0, 0, 0)
        cost1_0 = lax.dynamic_update_index_in_dim(
            jnp.zeros_like(cost0),
            jnp.sqrt(sn0 ** 2 + damp2 * _bdot(x, x)), 0, 0)
    body = _make_block_cgls_body(Op, xdt, damp2, floors, tol, M=M,
                                 normal=normal, guards=guards,
                                 stall_n=stall_n)
    if guards:
        K = kold.shape[0]
        state = head + (kold, jnp.asarray(0), cost0, cost1_0,
                        _status0(K), kold, jnp.zeros((K,), jnp.int32))

        def cond(st):
            return ((st[5] < niter)
                    & jnp.any((st[4] > tol)
                              & (st[8] == _rstatus.RUNNING)))

        out = lax.while_loop(cond, body, state)
        x, kold, iiter, cost, cost1, status = (out[0], out[4], out[5],
                                               out[6], out[7], out[8])
        return (x, iiter, cost, cost1, kold,
                _bresolve(status, kold, tol))

    def cond(st):
        return (st[5] < niter) & (jnp.max(st[4]) > tol)

    state = head + (kold, jnp.asarray(0), cost0, cost1_0)
    out = lax.while_loop(cond, body, state)
    return out[0], out[5], out[6], out[7], out[4]


# ------------------------------------------------------ public wrappers
def block_cg(Op, y: DistributedArray,
             x0: Optional[DistributedArray] = None, niter: int = 10,
             tol: float = 1e-4, guards: Optional[bool] = None,
             M=None):
    """Fused block CG: K RHS columns through one ``lax.while_loop``.

    ``y`` (and the optional ``x0``) are 2-D ``(n, K)``
    ``DistributedArray``\\ s — rows sharded, columns local. Returns
    ``(x, iiter, cost)`` with ``cost`` of shape ``(iiter+1, K)`` (one
    residual trajectory per column). Finished columns freeze in-loop;
    with guards on, per-column status words land in
    ``resilience.status.last_status("block_cg")["columns"]``.
    ``K=1`` routes through the single-RHS fused program — same cache
    entry, bit-identical HLO.

    ``PYLOPS_MPI_TPU_AUTODIFF=on`` reroutes traced inputs to the
    implicit-diff rule (one block backward solve covers all K
    cotangent columns) — see :func:`~pylops_mpi_tpu.solvers.basic.cg`;
    guards are excluded on the traced path."""
    _check_block(Op, y)
    from ..utils import deps as _deps
    if _deps.autodiff_enabled():
        from ..autodiff import implicit as _autodiff
        if _autodiff.should_intercept(Op, y, x0):
            return _autodiff.entry_block_cg(Op, y, x0, niter, tol, M)
    K = int(y.global_shape[1])
    x0_owned = x0 is None
    if x0 is None:
        x0 = _zero_block_model(Op, y)
    from ..resilience.status import guards_enabled
    use_guards = guards_enabled(guards)
    with _trace.span("solver.block_cg", cat="solver",
                     op=type(Op).__name__, shape=Op.shape, batch=K,
                     dtype=_vdtype(x0), niter=niter, tol=tol,
                     guards=use_guards,
                     telemetry=telemetry.telemetry_enabled()):
        if K == 1:
            from ..resilience import status as _rstatus
            from .basic import _run_cg_fused
            x1, iiter, cost, code = _run_cg_fused(
                Op, _squeeze_col(y), _squeeze_col(x0), True, niter,
                tol, use_guards, M=M)
            if use_guards:
                _rstatus.record_columns("block_cg", [code], iiter)
            return _expand_col(x1), iiter, np.asarray(cost)[:, None]
        from . import ca as _ca
        _ca_mode = _ca.resolve_mode(Op, "block_cg")
        if _ca_mode != "off":
            # K>1 communication-avoiding route (s-step pipelines: no
            # block Gram variant); K=1 already inherited CA above via
            # the single-RHS runner's own dispatch
            return _ca.run_block_cg(Op, y, x0, x0_owned, niter, tol,
                                    use_guards, M=M, mode=_ca_mode)
        if use_guards:
            from ..resilience import status as _rstatus
            stall_n = _rstatus.stall_window()
            fn = _get_fused(
                Op, (id(Op), "block_cg", niter, _vkey(y), _vkey(x0),
                     _rstatus.guards_signature(True)) + _mkey(M),
                lambda op: partial(_block_cg_fused, op, niter=niter,
                                   M=M, guards=True, stall_n=stall_n),
                donate_argnums=_DONATE_X0, keepalive=M,
                aot_eligible=(M is None))
            x, iiter, cost, status = fn(
                y, x0 if x0_owned else _donate_copy(x0), tol)
            iiter = int(iiter)
            _metrics.inc("solver.block_cg.solves")
            _metrics.inc("solver.block_cg.iterations", iiter)
            _rstatus.record_columns(
                "block_cg", [int(cd) for cd in np.asarray(status)],
                iiter)
            return x, iiter, np.asarray(cost)[:iiter + 1]
        fn = _get_fused(Op, (id(Op), "block_cg", niter, _vkey(y),
                             _vkey(x0)) + _mkey(M),
                        lambda op: partial(_block_cg_fused, op,
                                           niter=niter, M=M),
                        donate_argnums=_DONATE_X0, keepalive=M,
                        aot_eligible=(M is None))
        x, iiter, cost = fn(y, x0 if x0_owned else _donate_copy(x0),
                            tol)
        iiter = int(iiter)
        _metrics.inc("solver.block_cg.solves")
        _metrics.inc("solver.block_cg.iterations", iiter)
        return x, iiter, np.asarray(cost)[:iiter + 1]


def _run_block_cgls_fused(Op, y, x0, niter, damp, tol, M=None,
                          x0_owned: bool = False,
                          use_normal: bool = False):
    """Compile-cache-and-run the unguarded fused block-CGLS loop;
    raw ``(x, iiter, cost, cost1, kold)`` with ``(iiter+1, K)`` sliced
    histories — the :func:`~pylops_mpi_tpu.solvers.basic._run_cgls_fused`
    contract minus the status word. Factored out of :func:`block_cgls`
    (identical ``_get_fused`` key) so the autodiff tier's concrete
    forward (autodiff/implicit.py) reuses the SAME cached executables
    and AOT bank entries as plain solves instead of growing a parallel
    executable set. ``use_normal`` (the sweep schedule, resolved by the
    caller) is part of the key, and so of the AOT bank's: a flip never
    meets a stale executable."""
    with _trace.span("solver.launch", cat="solver", solver="block_cgls"):
        fn = _get_fused(Op, (id(Op), "block_cgls", use_normal, niter,
                             _vkey(y), _vkey(x0)) + _mkey(M),
                        lambda op: partial(_block_cgls_fused, op,
                                           niter=niter, M=M,
                                           normal=use_normal),
                        donate_argnums=_DONATE_X0, keepalive=M,
                        aot_eligible=(M is None))
        x, iiter, cost, cost1, kold = fn(
            y, x0 if x0_owned else _donate_copy(x0), damp, tol)
    with _trace.span("solver.collect", cat="solver", solver="block_cgls"):
        iiter = int(iiter)
        _count_cgls_solve(iiter, use_normal, "block_cgls")
        return (x, iiter, np.asarray(cost)[:iiter + 1],
                np.asarray(cost1)[:iiter + 1], np.asarray(kold))


def block_cgls(Op, y: DistributedArray,
               x0: Optional[DistributedArray] = None, niter: int = 10,
               damp: float = 0.0, tol: float = 1e-4,
               guards: Optional[bool] = None, M=None,
               normal: Optional[bool] = None):
    """Fused block CGLS; see :func:`block_cg`. Returns ``(x, istop,
    iiter, kold, r2norm, cost)`` — the
    :func:`~pylops_mpi_tpu.solvers.basic.cgls` shape with per-column
    ``istop``/``kold``/``r2norm`` vectors and a ``(iiter+1, K)`` cost
    history.

    ``normal`` picks the sweep schedule as in
    :func:`~pylops_mpi_tpu.solvers.basic.cgls`, through the same
    resolver (``basic._resolve_normal``): ``True`` is the one-sweep
    iteration (``(u, q) = Op.normal_matvec(c)`` for all K columns, the
    gradient recurrence per column), ``False`` the classic matvec +
    rmatvec pair, ``None`` (default) asks
    ``Op.prefers_fused_normal(x0)`` — one sweep only where a compiled
    one-sweep kernel beats two sweeps at this column count (a batched
    real ``MPIBlockDiag`` on a TPU, at the widths the chip has shown:
    ``pallas_kernels.normal_matvec_pays``); everything else, and every
    operator on the CPU, compiles the classic program. ``K=1`` hands
    the same answer to the single-RHS program; the
    communication-avoiding engine (``PYLOPS_MPI_TPU_CA``) takes it
    too. The traced autodiff forward keeps the classic schedule.

    ``PYLOPS_MPI_TPU_AUTODIFF=on`` reroutes traced inputs to the
    implicit-diff rule — see :func:`block_cg`."""
    _check_block(Op, y)
    from ..utils import deps as _deps
    if _deps.autodiff_enabled():
        from ..autodiff import implicit as _autodiff
        if _autodiff.should_intercept(Op, y, x0):
            return _autodiff.entry_block_cgls(Op, y, x0, niter, damp,
                                              tol, M)
    K = int(y.global_shape[1])
    x0_owned = x0 is None
    if x0 is None:
        x0 = _zero_block_model(Op, y)
    from ..resilience.status import guards_enabled
    use_guards = guards_enabled(guards)
    use_normal = _resolve_normal(Op, x0, normal)
    with _trace.span("solver.block_cgls", cat="solver",
                     op=type(Op).__name__, shape=Op.shape, batch=K,
                     dtype=_vdtype(x0), niter=niter, damp=damp, tol=tol,
                     normal=use_normal, guards=use_guards,
                     telemetry=telemetry.telemetry_enabled()):
        if K == 1:
            from ..resilience import status as _rstatus
            from .basic import _run_cgls_fused
            x1, iiter, cost, cost1, kold, code, _ = _run_cgls_fused(
                Op, _squeeze_col(y), _squeeze_col(x0), True, niter,
                damp, tol, use_normal, use_guards, M=M)
            if use_guards:
                _rstatus.record_columns("block_cgls", [code], iiter)
            kold = np.atleast_1d(np.asarray(kold))
            istop = np.where(kold < tol, 1, 2)
            return (_expand_col(x1), istop, iiter, kold,
                    np.atleast_1d(np.asarray(cost1)[-1]),
                    np.asarray(cost)[:, None])
        from . import ca as _ca
        _ca_mode = _ca.resolve_mode(Op, "block_cgls")
        if _ca_mode != "off":
            return _ca.run_block_cgls(Op, y, x0, x0_owned, niter, damp,
                                      tol, use_guards, M=M,
                                      mode=_ca_mode,
                                      use_normal=use_normal)
        if use_guards:
            from ..resilience import status as _rstatus
            with _trace.span("solver.launch", cat="solver",
                             solver="block_cgls"):
                stall_n = _rstatus.stall_window()
                fn = _get_fused(
                    Op, (id(Op), "block_cgls", use_normal, niter,
                         _vkey(y), _vkey(x0),
                         _rstatus.guards_signature(True)) + _mkey(M),
                    lambda op: partial(_block_cgls_fused, op,
                                       niter=niter, M=M,
                                       normal=use_normal, guards=True,
                                       stall_n=stall_n),
                    donate_argnums=_DONATE_X0, keepalive=M,
                    aot_eligible=(M is None))
                x, iiter, cost, cost1, kold, status = fn(
                    y, x0 if x0_owned else _donate_copy(x0), damp, tol)
            with _trace.span("solver.collect", cat="solver",
                             solver="block_cgls"):
                iiter = int(iiter)
                _count_cgls_solve(iiter, use_normal, "block_cgls")
                _rstatus.record_columns(
                    "block_cgls", [int(cd) for cd in np.asarray(status)],
                    iiter)
                kold = np.asarray(kold)
                istop = np.where(kold < tol, 1, 2)
                return (x, istop, iiter, kold,
                        np.asarray(cost1)[iiter],
                        np.asarray(cost)[:iiter + 1])
        x, iiter, cost, cost1, kold = _run_block_cgls_fused(
            Op, y, x0, niter, damp, tol, M=M, x0_owned=x0_owned,
            use_normal=use_normal)
        return (x, np.where(kold < tol, 1, 2), iiter, kold,
                cost1[-1], cost)


# ------------------------------------------------------ segmented blocks
def _block_cg_setup_builder(Op, *, niter, M=None):
    def setup(y, x0):
        x = x0
        r = y - Op.matvec(x)
        z = _precond_apply(M, r, _vdtype(x0))
        c = z
        kold = _bdot(r, z)
        floors = _mp_floor(kold)
        cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold),
                          dtype=jnp.asarray(kold).dtype)
        cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold),
                                                0, 0)
        return x, r, c, kold, cost0, floors

    return setup


def _block_cg_epoch_builder(Op, *, guards, stall_n, M=None):
    def run(y, x, r, c, kold, iiter, cost, status, bestk, stall,
            floors, tol, epoch_end):
        from ..resilience import status as _rstatus
        body = _make_block_cg_body(Op, _vdtype(x), floors, tol, M=M,
                                   guards=guards,
                                   carry_status=not guards,
                                   stall_n=stall_n)
        if guards:
            state = (x, r, c, kold, iiter, cost, status, bestk, stall)

            def cond(st):
                return ((st[4] < epoch_end)
                        & jnp.any((st[3] > tol)
                                  & (st[6] == _rstatus.RUNNING)))

            return lax.while_loop(cond, body, state)
        state = (x, r, c, kold, iiter, cost, status)

        def cond(st):
            return (st[4] < epoch_end) & (jnp.max(st[3]) > tol)

        out = lax.while_loop(cond, body, state)
        return out + (bestk, stall)

    return run


_BLOCK_CG_FIELDS = ("x", "r", "c", "kold", "iiter", "cost", "status",
                    "bestk", "stall")


def block_cg_segmented(Op, y: DistributedArray,
                       x0: Optional[DistributedArray] = None,
                       niter: int = 100, tol: float = 1e-4,
                       epoch: Optional[int] = None,
                       checkpoint_path: Optional[str] = None,
                       resume: bool = True,
                       backend: Optional[str] = None,
                       guards: Optional[bool] = None,
                       on_epoch=None, M=None):
    """Segmented block CG: epochs of fused block iterations with the
    whole ``(n, K)`` carry checkpointed between epochs
    (``utils/checkpoint.save_fused_carry`` round-trips any-ndim
    ``DistributedArray`` carries unchanged). A killed process
    re-invoking with the same ``checkpoint_path`` resumes from the
    last banked epoch; see :func:`~.segmented.cg_segmented` for the
    epoch/cadence contract. Returns ``(x, iiter, cost, status)`` with
    per-column status codes."""
    from .segmented import _FUSED_SCHEMA, _load_carry, resolve_epoch
    from ..resilience import status as _rstatus
    from ..resilience.status import guards_enabled, stall_window
    from ..utils import checkpoint as _ckpt
    from ..resilience.elastic import maybe_start_heartbeat
    _check_block(Op, y)
    maybe_start_heartbeat()
    K = int(y.global_shape[1])
    guards_on = guards_enabled(guards)
    stall_n = stall_window() if guards_on else 0
    E = resolve_epoch(epoch, niter)
    if x0 is None:
        x0 = _zero_block_model(Op, y)
    meta = {"niter": niter, "tol": float(tol), "guards": guards_on,
            "batch": K, "precond": _precond_signature(M)}
    state = (_load_carry(checkpoint_path, "block_cg", y.mesh, meta)
             if resume else None)
    resumed = state is not None
    fields = _BLOCK_CG_FIELDS

    with _trace.span("solver.block_cg_segmented", cat="solver",
                     op=type(Op).__name__, shape=Op.shape, batch=K,
                     niter=niter, epoch=E, guards=guards_on,
                     resumed=resumed,
                     checkpoint=bool(checkpoint_path)):
        if state is None:
            setup = _get_fused(
                Op, (id(Op), "block_cg-seg-setup", niter, _vkey(y),
                     _vkey(x0)) + _mkey(M),
                lambda op: _block_cg_setup_builder(op, niter=niter,
                                                   M=M),
                keepalive=M, aot_eligible=(M is None))
            x, r, c, kold, cost, floors = setup(y, x0)
            state = dict(zip(fields, [
                x, r, c, kold, jnp.asarray(0), cost, _status0(K),
                kold, jnp.zeros((K,), jnp.int32)]))
            state["floors"] = floors
        run = _get_fused(
            Op, (id(Op), "block_cg-seg", niter, _vkey(y), _vkey(x0),
                 ("guards", guards_on,
                  stall_n if guards_on else None)) + _mkey(M),
            lambda op: _block_cg_epoch_builder(op, guards=guards_on,
                                               stall_n=stall_n, M=M),
            keepalive=M, aot_eligible=(M is None))
        epochs = 0
        while True:
            iiter = int(state["iiter"])
            kold_np = np.asarray(state["kold"])
            codes = np.asarray(state["status"])
            live = ((kold_np > tol) & (codes == _rstatus.RUNNING)
                    & np.isfinite(kold_np))
            if iiter >= niter or not live.any():
                break
            epoch_end = min(iiter + E, niter)
            floors = state["floors"]
            out = run(y, *[state[f] for f in fields], floors, tol,
                      epoch_end)
            state = dict(zip(fields, out))
            state["floors"] = floors
            epochs += 1
            if checkpoint_path:
                carry = {**meta, "epoch": E, "schema": _FUSED_SCHEMA}
                carry.update({f: state[f] for f in fields})
                carry["floors"] = state["floors"]
                _ckpt.save_fused_carry(checkpoint_path, "block_cg",
                                       carry, backend=backend)
                _trace.event("solver.checkpoint", cat="resilience",
                             solver="block_cg",
                             iiter=int(state["iiter"]), epoch=epochs,
                             path=checkpoint_path)
            if on_epoch is not None:
                on_epoch({"epoch": epochs, "iiter": int(state["iiter"]),
                          "resid": float(jnp.max(jnp.asarray(
                              state["cost"])[int(state["iiter"])])),
                          "columns": [_rstatus.status_name(int(cd))
                                      for cd in
                                      np.asarray(state["status"])]})
        iiter = int(state["iiter"])
        kold_np = np.asarray(state["kold"])
        codes = np.asarray(state["status"])
        final = np.where(
            codes != _rstatus.RUNNING, codes,
            np.where(~np.isfinite(kold_np), _rstatus.BREAKDOWN,
                     np.where(kold_np <= tol, _rstatus.CONVERGED,
                              _rstatus.MAXITER))).astype(np.int32)
        if guards_on:
            _rstatus.record_columns("block_cg",
                                    [int(cd) for cd in final], iiter)
        cost = np.asarray(state["cost"])[:iiter + 1]
        return state["x"], iiter, cost, final


# ------------------------------------------- vmap over operator params
BatchedResult = namedtuple("BatchedResult",
                           ["xs", "iiter", "cost", "cost1", "kold"])
BatchedResult.__doc__ = (
    "Result of a vmap-over-parameters batched solve: ``xs`` is the "
    "list of per-problem model vectors; ``iiter``/``cost`` (and for "
    "CGLS ``cost1``/``kold``) carry a leading problem axis. ``cost`` "
    "rows past a problem's own ``iiter`` are zeros — the batch runs "
    "until every problem's loop exits.")

_BATCHED_CACHE: "OrderedDict" = OrderedDict()


def _batched_cache_max() -> int:
    """``PYLOPS_MPI_TPU_BATCHED_CACHE`` — capacity of the per-family
    compiled-executable LRU (default 8, floored at 1 so a typo cannot
    disable caching entirely)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_BATCHED_CACHE", "8"))
    except ValueError:
        v = 8
    return max(1, v)


def batched_cache_info() -> dict:
    """Introspection for the warm pool / tests: the batched-solve LRU's
    ``{"size", "max", "families"}`` where ``families`` lists the cached
    ``(solver, niter, B, op)`` heads newest-last. Hit/miss traffic is
    on the metrics counters ``solver.batched.cache.hit`` / ``.miss``
    (the ``tuning.cache.*`` idiom)."""
    return {"size": len(_BATCHED_CACHE),
            "max": _batched_cache_max(),
            "families": [k[:4] for k in _BATCHED_CACHE]}


def _aval_key(t):
    return tuple((tuple(l.shape), str(l.dtype))
                 for l in jax.tree_util.tree_leaves(t))


def batched_solve(factory, params: Sequence, ys: Sequence,
                  *, solver: str = "cgls",
                  x0s: Optional[Sequence] = None, niter: int = 10,
                  damp: float = 0.0, tol: float = 1e-4) -> BatchedResult:
    """Solve a FAMILY of same-shape problems — one compile.

    ``factory(p)`` builds the operator for parameter pytree ``p``;
    the B operators must be the same registered-pytree class
    (``linearoperator.register_operator_arrays``) with identical
    shapes, differing only in tensor data (e.g. many MDC chains with
    different kernels). Their array leaves are stacked and the
    single-RHS fused loop (``solver`` in ``{"cg", "cgls"}``) is
    ``jax.vmap``-ed over the stacked operator, data and model — the
    whole family shares ONE compiled program, cached across calls.
    Each problem's ``while_loop`` lane freezes when its own
    convergence test passes (the vmap batching rule masks finished
    lanes). Guards are not traced into the vmapped program — use the
    block solvers for per-problem status words.

    The stacked ``x0`` buffer is donated (when the donation gate is
    on), like the single-solve path."""
    from ..linearoperator import operator_is_jit_arg
    from ..ops._precision import donation_enabled
    from .basic import _cg_fused, _cgls_fused, _zero_like_model
    if solver not in ("cg", "cgls"):
        raise ValueError(f"solver={solver!r}: expected 'cg' or 'cgls'")
    params = list(params)
    ys = list(ys)
    if not params or len(params) != len(ys):
        raise ValueError(
            f"need one y per parameter set, got {len(params)} params "
            f"and {len(ys)} ys")
    ops = [factory(p) for p in params]
    Op0 = ops[0]
    if not operator_is_jit_arg(Op0):
        raise TypeError(
            f"batched_solve needs a registered pytree operator class "
            f"(linearoperator.register_operator_arrays); "
            f"{type(Op0).__name__} is not registered")
    for op in ops[1:]:
        if type(op) is not type(Op0) or op.shape != Op0.shape:
            raise ValueError(
                "batched_solve needs a same-shape operator family; got "
                f"{type(Op0).__name__}{Op0.shape} and "
                f"{type(op).__name__}{op.shape}")
    B = len(ops)
    stack = lambda *ls: jnp.stack(ls)
    # the operator pytree's aux is the instance itself (treedefs of two
    # family members never compare equal), so stack leaf-wise by hand
    # and unflatten with the first member's treedef
    leaves0, treedef0 = jax.tree_util.tree_flatten(Op0)
    if not leaves0:
        # zero array leaves would make every lane silently replay
        # member 0's arrays out of the treedef aux (e.g. an
        # MPIBlockDiag whose block count is not a multiple of the
        # device count never builds its stacked `_batched` leaf)
        raise ValueError(
            f"{type(Op0).__name__} flattens to no array leaves in this "
            "configuration, so nothing varies across the family; "
            "batched_solve cannot vmap it — solve the members "
            "individually (for MPIBlockDiag, the stacked-GEMM leaf "
            "needs the block count to be a multiple of the device "
            "count)")
    fam_leaves = [leaves0] + [jax.tree_util.tree_leaves(op)
                              for op in ops[1:]]
    for i, ls in enumerate(fam_leaves[1:], start=1):
        if len(ls) != len(leaves0) or any(
                jnp.shape(a) != jnp.shape(b) or
                jnp.asarray(a).dtype != jnp.asarray(b).dtype
                for a, b in zip(ls, leaves0)):
            raise ValueError(
                f"operator {i} flattens to different leaf avals than "
                "operator 0; batched_solve needs a same-shape family")
    OpB = jax.tree_util.tree_unflatten(
        treedef0, [stack(*ls) for ls in zip(*fam_leaves)])
    YB = jax.tree_util.tree_map(stack, *ys)
    if x0s is None:
        x0s = [_zero_like_model(op, yv) for op, yv in zip(ops, ys)]
    else:
        x0s = [x.copy() for x in x0s]  # donated below; keep callers' own
    X0B = jax.tree_util.tree_map(stack, *x0s)
    donate = (2,) if donation_enabled() else ()
    key = (solver, niter, B, type(Op0).__name__, _aval_key(OpB),
           _vkey(ys[0]), _vkey(x0s[0]), donate,
           telemetry.telemetry_signature())
    jfn = _BATCHED_CACHE.get(key)
    _metrics.inc("solver.batched.cache.hit" if jfn is not None
                 else "solver.batched.cache.miss")
    with _trace.span(f"solver.batched_{solver}", cat="solver",
                     op=type(Op0).__name__, shape=Op0.shape, family=B,
                     niter=niter, tol=tol, compiled=jfn is not None,
                     telemetry=telemetry.telemetry_enabled()):
        if jfn is None:
            if solver == "cg":
                one = lambda op, yv, xv, d, t: _cg_fused(op, yv, xv, t,
                                                         niter=niter)
            else:
                one = lambda op, yv, xv, d, t: _cgls_fused(
                    op, yv, xv, d, t, niter=niter)
            jfn = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, None, None)),
                          donate_argnums=donate)
            _BATCHED_CACHE[key] = jfn
            if len(_BATCHED_CACHE) > _batched_cache_max():
                _BATCHED_CACHE.popitem(last=False)
        else:
            _BATCHED_CACHE.move_to_end(key)
        out = jfn(OpB, YB, X0B, damp, tol)
        X = out[0]
        xs = [jax.tree_util.tree_map(lambda l: l[i], X)
              for i in range(B)]
        if solver == "cg":
            return BatchedResult(xs=xs, iiter=np.asarray(out[1]),
                                 cost=np.asarray(out[2]), cost1=None,
                                 kold=None)
        return BatchedResult(xs=xs, iiter=np.asarray(out[1]),
                             cost=np.asarray(out[2]),
                             cost1=np.asarray(out[3]),
                             kold=np.asarray(out[4]))
