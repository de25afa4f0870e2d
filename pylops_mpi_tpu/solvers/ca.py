"""Communication-avoiding Krylov tier (``PYLOPS_MPI_TPU_CA``).

Every classic fused CG/CGLS iteration pays 2-5 separate ``_rdot``
all-reduces (solvers/basic.py), each a latency-bound collective whose
scalar result sits on the recurrence critical path — on a DCN-connected
pod the per-collective wire latency, not bandwidth, becomes the
iteration floor ("Large Scale Distributed Linear Algebra With TPUs",
2112.09017, hits exactly this wall at pod scale). This module trades
a little algebra and a little roundoff head-room for fewer, earlier
collectives:

- **pipelined PCG / PCGLS** (:func:`run_cg_fused` / :func:`run_cgls_fused`
  with ``mode="pipelined"``): Ghysels–Vanroose-style recurrences carry
  the auxiliary vectors ``u = M r``, ``w = A u``, ``z = A M w`` companions
  so BOTH per-iteration dot products — ``γ = (r, u)`` and ``δ = (w, u)``
  — stack into ONE small vector reduced by a single all-reduce
  (:func:`_stacked_rdot`), issued at the TOP of the body so XLA can
  overlap the collective with the operator apply that follows. Lowered
  HLO carries exactly one ``all-reduce`` in the while body
  (``utils.hlo.assert_single_reduction``) vs 2 (CG) / up to 5 (CGLS)
  classic. CGLS runs pipelined CG on the damped normal system
  ``(AᴴA + damp²I) x = Aᴴ y`` — its ``cost``/``cost1`` lanes therefore
  record the preconditioned NORMAL-residual norm ``sqrt(γ)``, not the
  data-residual norm the classic engine logs.
- **s-step CA-CG** (``mode="sstep"``): each outer step grows monomial
  Krylov chains ``{(MA)^j p}`` and ``{(MA)^j z}`` locally (2s-1 operator
  applies), then pays ONE Gram-matrix all-reduce for everything s
  iterations of CG need — the coordinate recurrences run on replicated
  (2s+1)-vectors with zero further communication. The monomial basis
  conditions like κ(A)^s, so a breakdown guard (non-finite or
  non-positive pivot) rejects the outer update, raises
  ``status=BREAKDOWN`` (the PR 6 status word), and the host wrapper
  falls back to the pipelined engine from the last completed outer
  iterate (:func:`last_fallback` reports it). s-step is restricted to
  plain even unmasked real ``DistributedArray`` spaces; anything else
  silently uses the pipelined engine.

Mode selection is ``PYLOPS_MPI_TPU_CA=off|pipelined|sstep|auto``
(utils/deps.py). ``auto`` consults the α-β latency term the PR 11/17
cost model carries (``diagnostics.costmodel.roofline`` ``latency``
component vs the bandwidth bound) and NEVER chooses s-step on its own.
``off`` never reaches this module — the classic engines trace
bit-identical programs under unchanged cache keys.

Composition contracts (pinned by tests/test_ca.py):

- the ``M=`` seam: every engine takes the PR 15 preconditioner, and
  ``M=None`` drops the ``u``/``q`` carries entirely (they alias ``r``/
  ``s``), so unpreconditioned solves trace the lean program;
- PR 6 guards: the same reject-poisoned-update / breakdown / stagnation
  carry as the classic bodies, via the shared ``_guard_update``;
- PR 8 blocks: :func:`run_block_cg` / :func:`run_block_cgls` carry
  ``(K,)`` recurrence lanes with the same per-column freeze and
  per-column status words (``_bguard_update``);
- PR 6/8 segmented checkpoints: the ``*_seg_*`` builders expose the CA
  carries to ``solvers/segmented.py``; carries are stamped with the CA
  mode and :data:`CA_SCHEMA`, and a resume under a different mode
  refuses (``resume must replay the same plan``).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..distributedarray import DistributedArray
from ..stacked import StackedDistributedArray
from ..diagnostics import metrics as _metrics
from ..diagnostics import telemetry, trace as _trace
from .basic import (_DONATE_X0, _count_cgls_solve, _donate_copy,
                    _get_fused, _guard_update,
                    _i32, _mkey, _mp_floor, _precond_apply, _rdot,
                    _reject, _resolve_status, _step_scalar, _vdtype,
                    _vkey)
from .block import _bdot, _bguard_update, _bresolve, _status0

__all__ = ["resolve_mode", "ca_key", "classic_reductions_per_iter",
           "ca_reductions_per_iter", "last_fallback", "clear_fallback",
           "CA_SCHEMA"]

# CA while-loop carries are a different pytree than the classic
# engines' — segmented checkpoints written by this tier stamp this
# schema (classic carries keep _FUSED_SCHEMA=1) so a resume can never
# feed one engine's carry to the other.
CA_SCHEMA = 2

# stagnation window used when a status word is carried WITHOUT guards
# (the s-step engine always carries one for its breakdown verdict):
# effectively infinite, so only BREAKDOWN can fire.
_NO_STALL = 1 << 30

# classic fused engines' all-reduces per iteration — the α-term seed
# for the auto selector and the bench's reduction-count baseline.
_CLASSIC_REDUCTIONS = {"cg": 2, "cgls": 5, "block_cg": 2,
                       "block_cgls": 5}


def classic_reductions_per_iter(solver: str) -> int:
    """All-reduces per iteration of the CLASSIC fused engine."""
    return _CLASSIC_REDUCTIONS.get(solver, 2)


def ca_reductions_per_iter(mode: str, s: int = 1) -> float:
    """All-reduces per iteration under a CA mode: pipelined = 1,
    s-step = 1/s (one Gram reduction per s iterations)."""
    if mode == "sstep":
        return 1.0 / max(1, int(s))
    if mode == "pipelined":
        return 1.0
    return float(_CLASSIC_REDUCTIONS["cg"])


# ------------------------------------------------------ mode selection
def _auto_mode(Op, solver: str) -> str:
    """Latency-aware α-β selection: pipeline when the per-iteration
    reduction latency is a material fraction of the bandwidth-bound
    iteration time. Never chooses s-step (its basis conditioning is an
    opt-in risk). Unknown chips (no roofline) fall back to the explicit
    latency seam: an armed ``PYLOPS_MPI_TPU_REDUCE_STALL`` says the
    operator lives on a latency-dominated fabric (the CPU-sim bench
    shape), anything else stays classic."""
    try:
        from ..diagnostics import costmodel as _cm
        peaks = _cm.device_peaks()
        lat = peaks.get("allreduce_latency_s")
        if not lat:
            return "off"
        alpha_s = classic_reductions_per_iter(solver) * lat
        cost = _cm.estimate(Op)
        if cost is not None:
            rf = _cm.roofline(cost, peaks)
            pred = rf.get("predicted_s")
            if pred:
                return "pipelined" if alpha_s >= 0.25 * pred else "off"
    except Exception:
        return "off"
    from ..utils import deps as _deps
    return "pipelined" if _deps.reduce_stall_steps() else "off"


def resolve_mode(Op=None, solver: str = "cg") -> str:
    """Resolve ``PYLOPS_MPI_TPU_CA`` to a concrete engine for this
    solve: ``off`` | ``pipelined`` | ``sstep``."""
    from ..utils import deps as _deps
    mode = _deps.ca_mode()
    if mode == "auto":
        mode = _auto_mode(Op, solver)
    return mode


def ca_key(mode: str, s: Optional[int] = None):
    """Cache-key fragment for a CA engine. ``off`` contributes NOTHING
    so classic entries keep their pre-CA keys byte-identical."""
    if mode == "off":
        return ()
    if mode == "sstep":
        return (("ca", "sstep", int(s)),)
    return (("ca", mode),)


# ------------------------------------------------------ fallback events
_FB_LOCK = threading.Lock()
_LAST_FALLBACK: Optional[dict] = None


def _record_fallback(solver: str, s: int, iiter: int) -> None:
    global _LAST_FALLBACK
    with _FB_LOCK:
        _LAST_FALLBACK = {"solver": solver, "s": int(s),
                          "iteration": int(iiter)}
    _metrics.inc("solver.ca.sstep_fallbacks")
    _trace.event("solver.ca.sstep_fallback", cat="solver",
                 solver=solver, s=int(s), iteration=int(iiter))


def last_fallback() -> Optional[dict]:
    """The most recent s-step→pipelined breakdown fallback (``{solver,
    s, iteration}``), or ``None`` — the PR 6 escalation ladder's view
    into the basis-conditioning guard."""
    with _FB_LOCK:
        return dict(_LAST_FALLBACK) if _LAST_FALLBACK else None


def clear_fallback() -> None:
    global _LAST_FALLBACK
    with _FB_LOCK:
        _LAST_FALLBACK = None


# ------------------------------------------------------ stacked reductions
def _fusable(vs) -> bool:
    """True when the recurrence dots over these vectors can share one
    physical all-reduce: plain (non-stacked) DistributedArrays, no
    sub-communicator mask, uniform physical split, matching shapes."""
    shapes = set()
    for v in vs:
        if not isinstance(v, DistributedArray):
            return False
        if v.mask is not None or not v._even:
            return False
        shapes.add(v._arr.shape)
    return len(shapes) == 1


def _stacked_rdot(pairs):
    """The tentpole reduction: m recurrence dot products stacked into
    one small vector BEFORE the collective, so the lowered HLO carries
    a single ``all-reduce`` of m scalars instead of m latency-bound
    round trips. Falls back to per-pair :func:`basic._rdot` (one
    collective each — and one ``reduce_stall`` each, so the latency
    seam stays per-collective-honest) for stacked/ragged/masked
    spaces."""
    from ..ops._precision import accum_dtype, reduction_dtype
    from ..parallel.collectives import reduce_stall
    flat = [v for p in pairs for v in p]
    if not _fusable(flat):
        return jnp.stack([_rdot(u, v) for (u, v) in pairs])
    rdt = reduction_dtype(_vdtype(pairs[0][0]))
    acc = accum_dtype(pairs[0][0]._arr.dtype)
    zs = [(u._arr * jnp.conj(v._arr)).astype(acc).reshape(-1)
          for (u, v) in pairs]
    k = jnp.abs(jnp.sum(jnp.stack(zs, axis=0), axis=-1)).astype(rdt)
    return reduce_stall(k)


def _stacked_bdot(pairs):
    """Block twin of :func:`_stacked_rdot`: m per-column dots over
    ``(n, K)`` block vectors → one all-reduce of an ``(m, K)`` tile.
    Ragged row splits mask their padding rows exactly as
    ``DistributedArray.col_dot`` does."""
    from ..ops._precision import accum_dtype, reduction_dtype
    from ..parallel.collectives import reduce_stall
    ref = pairs[0][0]
    rdt = reduction_dtype(_vdtype(ref))
    acc = accum_dtype(ref._arr.dtype)
    # every operand repacks into the FIRST pair element's physical
    # layout (operator outputs of a ragged split can pad differently
    # than RHS-derived vectors), so the m tiles stack into one buffer
    # and lower to a single fused reduction
    mask = None if ref._even else ref._valid_phys_mask()
    zs = []
    for (u, v) in pairs:
        z = (jnp.conj(ref._operand_phys(u))
             * ref._operand_phys(v)).astype(acc)
        if mask is not None:
            z = jnp.where(mask, z, 0)
        zs.append(z)
    k = jnp.abs(jnp.sum(jnp.stack(zs, axis=0), axis=1)).astype(rdt)
    return reduce_stall(k)


# ------------------------------------------------------ pipelined engine
def _make_pipe_body(applyA, xdt, floors, tol, *, M=None, guards=False,
                    carry_status=False, stall_n=0, block=False,
                    fault=None, name="cg"):
    """Pipelined (P)CG loop body over the carry ``(x, r[, u], w, z[,
    q], s, p, kold, aold, iiter, cost[, status][, bestk, stall])``.

    Invariants carried: ``u = M r`` (dropped when ``M is None`` —
    ``u`` IS ``r``), ``w = A u``; auxiliary directions ``z = A M w``-,
    ``q = M w``-, ``s = w``-, ``p = u``-companions of the classic
    search direction. Both dots — ``γ = (r, u)`` and the pipelined
    pivot ``δ = (w, u)`` — are issued as ONE stacked reduction at the
    top of the body, BEFORE the operator apply ``n = A M w``, so the
    collective and the matvec overlap. ``kold`` carries γ, which makes
    the loop's stopping test lag one iteration behind the classic
    engine (cost lane j holds the residual of iterate j-1; iteration
    counts agree within +1).

    ``block=True`` swaps per-column ``(K,)`` recurrence lanes, the
    ``max(floors, tol)`` per-column freeze and per-column guard
    verdicts in — the same unified body serves all four pipelined
    engines."""
    from ..resilience import faults as _faults, status as _rstatus
    from .basic import _fault_sites
    precond = M is not None
    nan_at, stall_at = _fault_sites(guards, fault)
    dot2 = _stacked_bdot if block else _stacked_rdot

    def body(state):
        if precond:
            x, r, u, w, z, q, s, p = state[:8]
            rest = state[8:]
        else:
            x, r, w, z, s, p = state[:6]
            u, q = r, s
            rest = state[6:]
        if guards:
            kold, aold, iiter, cost, status, bestk, stall = rest
        elif carry_status:
            kold, aold, iiter, cost, status = rest
            bestk = stall = None
        else:
            kold, aold, iiter, cost = rest
            status = bestk = stall = None
        # the single reduction, first — everything below overlaps it
        g = dot2(((r, u), (w, u)))
        gamma, delta = g[0], g[1]
        m = _precond_apply(M, w, xdt)
        n = applyA(m)
        if nan_at is not None:
            n = _faults.inject_nan(n, iiter, nan_at)
        # block freeze tests the CARRIED γ (kold), not the one just
        # reduced: the single-RHS while-cond exits after the body has
        # applied the update its own γ drove, so a column must apply
        # that same last update before freezing — per-column iterates
        # stay bit-identical to their single-RHS solves
        done = (kold <= jnp.maximum(floors, tol)) if block \
            else (gamma <= floors)
        if block and (guards or carry_status):
            done = done | (status != _rstatus.RUNNING)
        zero = jnp.zeros_like(gamma)
        b = jnp.where((iiter == 0) | done, zero, gamma / kold)
        a = jnp.where(done, zero, gamma / (delta - b * gamma / aold))
        if stall_at is not None:
            a = _faults.inject_stall(a, iiter, stall_at)
        bs = _step_scalar(b, xdt)
        as_ = _step_scalar(a, xdt)
        zn = n + z * bs
        sn = w + s * bs
        pn = u + p * bs
        if precond:
            qn = m + q * bs
            un = u - qn * as_
        xn = x + pn * as_
        rn = r - sn * as_
        wn = w - zn * as_
        k = gamma
        if guards:
            if block:
                bad = ((~jnp.isfinite(a)) | (~jnp.isfinite(b))
                       | (~jnp.isfinite(gamma)) | (~jnp.isfinite(delta)))
            else:
                bad = (jnp.any(~jnp.isfinite(a))
                       | jnp.any(~jnp.isfinite(b))
                       | jnp.any(~jnp.isfinite(gamma))
                       | jnp.any(~jnp.isfinite(delta)))
            x = _reject(bad, x, xn)
            r = _reject(bad, r, rn)
            w = _reject(bad, w, wn)
            z = _reject(bad, z, zn)
            s = _reject(bad, s, sn)
            p = _reject(bad, p, pn)
            if precond:
                u = _reject(bad, u, un)
                q = _reject(bad, q, qn)
            k = jnp.where(bad, kold, gamma)
            upd = _bguard_update if block else _guard_update
            status, bestk, stall = upd(status, bestk, stall, bad, k,
                                       done, stall_n)
            aold = jnp.where(bad | done, aold, a)
        else:
            x, r, w, z, s, p = xn, rn, wn, zn, sn, pn
            if precond:
                u, q = un, qn
            aold = jnp.where(done, aold, a)
        iiter = iiter + 1
        cost = lax.dynamic_update_index_in_dim(cost, jnp.sqrt(k), iiter, 0)
        telemetry.iteration(name, iiter, resid=jnp.sqrt(k), k=k, alpha=a)
        head = (x, r, u, w, z, q, s, p) if precond else (x, r, w, z, s, p)
        if guards:
            return head + (k, aold, iiter, cost, status, bestk, stall)
        if carry_status:
            return head + (k, aold, iiter, cost, status)
        return head + (k, aold, iiter, cost)

    return body


def _pipe_seed(applyA, dot1, r, u, niter, precond, x):
    """Shared tail of the pipelined setups: seed ``w``, the recurrence
    scalars and the alias head (the first body overwrites every
    auxiliary direction because ``b = 0`` at ``iiter == 0``, so they
    start as aliases — no extra buffers, no extra flops)."""
    w = applyA(u)
    kold = dot1(r, u)
    floors = _mp_floor(kold)
    aold = jnp.ones_like(kold)
    cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold),
                      dtype=jnp.asarray(kold).dtype)
    cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold), 0, 0)
    if precond:
        head = (x, r, u, w, w, u, w, u)
    else:
        head = (x, r, w, w, w, r)
    return head, kold, floors, aold, cost0


def _pipe_cg_seed(Op, y, x0, *, niter, M, block):
    xdt = _vdtype(x0)
    x = x0  # donated: the carry aliases the caller's buffer in place
    r = y - Op.matvec(x)
    u = _precond_apply(M, r, xdt)
    dot1 = _bdot if block else _rdot
    return _pipe_seed(Op.matvec, dot1, r, u, niter, M is not None, x)


def _normal_apply(Op, damp2, xdt, normal):
    """``v → (AᴴA + damp²I) v`` — the operator the pipelined CGLS body
    iterates on. ``normal=True`` uses the one-sweep fused
    ``Op.normal_matvec`` (the schedule ``cgls`` resolved: basic.py
    ``_resolve_normal``)."""
    d2 = _step_scalar(damp2, xdt)
    if normal:
        def applyA(v):
            u2, _ = Op.normal_matvec(v)
            return u2 + v * d2
    else:
        def applyA(v):
            return Op.rmatvec(Op.matvec(v)) + v * d2
    return applyA


def _pipe_cgls_seed(Op, y, x0, damp, damp2, *, niter, normal, M, block):
    """Pipelined CGLS setup. Matches the classic ``_cgls_setup``
    recurrence seed exactly — including the reference quirk of damping
    the initial residual by ``damp`` (not ``damp²``) — so ``kold``,
    ``floors`` and ``cost[0]`` agree with the classic engine; the
    carried residual is the TRUE damped normal residual."""
    xdt = _vdtype(x0)
    applyA = _normal_apply(Op, damp2, xdt, normal)
    dot1 = _bdot if block else _rdot
    x = x0
    s0 = y - Op.matvec(x)
    rq = Op.rmatvec(s0) - x * _step_scalar(damp, xdt)
    zq = _precond_apply(M, rq, xdt)
    kold = dot1(rq, zq)
    floors = _mp_floor(kold)
    r = rq + x * _step_scalar(damp - damp2, xdt)
    u = _precond_apply(M, r, xdt)
    w = applyA(u)
    aold = jnp.ones_like(kold)
    cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold),
                      dtype=jnp.asarray(kold).dtype)
    cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold), 0, 0)
    if M is not None:
        head = (x, r, u, w, w, u, w, u)
    else:
        head = (x, r, w, w, w, r)
    return head, kold, floors, aold, cost0, applyA


def _pipe_loop(body, head, kold, aold, cost0, niter, tol, *, guards,
               block, precond):
    """Assemble carry + cond and run the pipelined while_loop; returns
    ``(x, kold, iiter, cost[, resolved_status])``."""
    from ..resilience import status as _rstatus
    nh = 8 if precond else 6
    base = head + (kold, aold, jnp.asarray(0), cost0)
    if guards:
        if block:
            K = kold.shape[0]
            st0 = (_status0(K), kold, jnp.zeros((K,), jnp.int32))
        else:
            st0 = (_i32(_rstatus.RUNNING), jnp.max(kold), _i32(0))
        state = base + st0

        if block:
            def cond(st):
                return ((st[nh + 2] < niter)
                        & jnp.any((st[nh] > tol)
                                  & (st[nh + 4] == _rstatus.RUNNING)))
        else:
            def cond(st):
                return ((st[nh + 2] < niter)
                        & (jnp.max(st[nh]) > tol)
                        & (st[nh + 4] == _rstatus.RUNNING))

        out = lax.while_loop(cond, body, state)
        resolve = _bresolve if block else _resolve_status
        return (out[0], out[nh], out[nh + 2], out[nh + 3],
                resolve(out[nh + 4], out[nh], tol))

    def cond(st):
        return (st[nh + 2] < niter) & (jnp.max(st[nh]) > tol)

    out = lax.while_loop(cond, body, state := base)
    return out[0], out[nh], out[nh + 2], out[nh + 3]


def _pipe_cg_fused(Op, y, x0, tol, *, niter, M=None, guards=False,
                   stall_n=0, fault=None, block=False):
    """Whole pipelined (P)CG solve as one ``lax.while_loop`` — the CA
    twin of ``basic._cg_fused`` (same return contract).

    Also the autodiff tier's traced CA seam (autodiff/implicit.py):
    under a non-``off`` CA mode, traced forward/backward solves inline
    THIS builder for both ``pipelined`` and ``sstep`` — the s-step
    engine's host-side breakdown fallback (``run_sstep_*``) cannot run
    inside a trace, and the pipelined twin is its communication
    equivalent (one fused reduction per iteration)."""
    head, kold, floors, aold, cost0 = _pipe_cg_seed(
        Op, y, x0, niter=niter, M=M, block=block)
    body = _make_pipe_body(Op.matvec, _vdtype(x0), floors, tol, M=M,
                           guards=guards, stall_n=stall_n, block=block,
                           fault=fault,
                           name="block_cg" if block else "cg")
    out = _pipe_loop(body, head, kold, aold, cost0, niter, tol,
                     guards=guards, block=block, precond=M is not None)
    if guards:
        x, kold, iiter, cost, status = out
        return x, iiter, cost, status
    x, kold, iiter, cost = out
    return x, iiter, cost


def _pipe_cgls_fused(Op, y, x0, damp, tol, *, niter, normal=False,
                     M=None, guards=False, stall_n=0, fault=None,
                     block=False):
    """Whole pipelined (P)CGLS solve — pipelined CG on the damped
    normal system; return contract of ``basic._cgls_fused_any``
    (``cost1`` aliases ``cost``: both lanes are the normal-residual
    norm here)."""
    damp2 = damp ** 2
    head, kold, floors, aold, cost0, applyA = _pipe_cgls_seed(
        Op, y, x0, damp, damp2, niter=niter, normal=normal, M=M,
        block=block)
    body = _make_pipe_body(applyA, _vdtype(x0), floors, tol, M=M,
                           guards=guards, stall_n=stall_n, block=block,
                           fault=fault,
                           name="block_cgls" if block else "cgls")
    out = _pipe_loop(body, head, kold, aold, cost0, niter, tol,
                     guards=guards, block=block, precond=M is not None)
    if guards:
        x, kold, iiter, cost, status = out
        return x, iiter, cost, cost, kold, status
    x, kold, iiter, cost = out
    return x, iiter, cost, cost, kold


# ------------------------------------------------------ s-step engine
def _sstep_eligible(*vs) -> bool:
    """s-step needs the fused Gram matmul: plain even unmasked real
    DistributedArray spaces only (signed inner products — ``abs`` would
    corrupt the coordinate recurrences, so complex is out)."""
    for v in vs:
        if not isinstance(v, DistributedArray):
            return False
        if v.mask is not None or not v._even:
            return False
        if np.issubdtype(np.dtype(v.dtype), np.complexfloating):
            return False
    return True


def _sstep_maps(s: int):
    """Static coordinate operators for the 2s+1-column combined basis
    ``V = [V_0..V_s | Z_0..Z_{s-1}]`` with products ``W = [W_0..W_{s-1}
    | Y_0..Y_{s-2}]`` (``W_j = A V_j``, ``Y_j = A Z_j``):
    ``Amap`` maps V-coordinates to W-coordinates of ``A·``, ``Smap``
    shifts V-coordinates by one application of ``M A``. Degrees stay in
    range by construction: at inner step j the direction has V-degree j
    (≤ s-1) and the residual-companion Z-degree j-1 (≤ s-2)."""
    nv, nw = 2 * s + 1, 2 * s - 1
    Amap = np.zeros((nw, nv))
    Smap = np.zeros((nv, nv))
    for j in range(s):
        Amap[j, j] = 1.0            # A V_j = W_j
        Smap[j + 1, j] = 1.0        # (MA) V_j = V_{j+1}
    for j in range(s - 1):
        Amap[s + j, s + 1 + j] = 1.0        # A Z_j = Y_j
        Smap[s + 2 + j, s + 1 + j] = 1.0    # (MA) Z_j = Z_{j+1}
    return Amap, Smap


def _make_sstep_body(Op, xdt, floors, tol, *, s, niter, M=None,
                     guards=False, stall_n=0):
    """s-step CA-CG outer body: build the monomial block (2s-1 operator
    applies, local), pay ONE Gram all-reduce, run s coordinate-space CG
    steps (replicated small vectors, zero communication), recombine.
    A non-finite or non-positive pivot is the monomial-basis
    conditioning guard: the whole outer update is rejected (the carry
    keeps the last completed outer iterate) and ``status=BREAKDOWN``."""
    from ..ops._precision import accum_dtype
    from ..parallel.collectives import reduce_stall
    from ..resilience import status as _rstatus
    precond = M is not None
    Amap_np, Smap_np = _sstep_maps(s)
    nv, nw = 2 * s + 1, 2 * s - 1

    def body(state):
        if precond:
            x, r, p, z = state[:4]
            rest = state[4:]
        else:
            x, r, p = state[:3]
            z = r
            rest = state[3:]
        kold, iiter, cost, status, bestk, stall = rest
        acc = accum_dtype(x._arr.dtype)
        Amap = jnp.asarray(Amap_np, acc)
        Smap = jnp.asarray(Smap_np, acc)
        # monomial chains: V from the direction p, Z from the
        # (preconditioned) residual z — all operator applies, no dots
        V_cols, W_cols = [p], []
        v = p
        for _ in range(s):
            Av = Op.matvec(v)
            W_cols.append(Av)
            v = _precond_apply(M, Av, xdt)
            V_cols.append(v)
        Z_cols, Y_cols = [z], []
        zc = z
        for _ in range(s - 1):
            Az = Op.matvec(zc)
            Y_cols.append(Az)
            zc = _precond_apply(M, Az, xdt)
            Z_cols.append(zc)
        Vm = jnp.stack([c._arr for c in V_cols + Z_cols],
                       axis=0).astype(acc)              # (2s+1, n)
        Wm = jnp.stack([c._arr for c in W_cols + Y_cols] + [r._arr],
                       axis=0).astype(acc)              # (2s, n)
        # THE one collective of the outer step: every inner product s
        # iterations of CG will touch, in a single (2s+1, 2s) tile
        Gall = reduce_stall(Vm @ Wm.T)
        G = Gall[:, :nw]        # (2s+1, 2s-1): (V_i, W_j)
        g0 = Gall[:, nw]        # (2s+1,):      (V_i, r0)
        cp = jnp.zeros((nv,), acc).at[0].set(1.0)       # p = V_0
        cz = jnp.zeros((nv,), acc).at[s + 1].set(1.0)   # z = Z_0
        d = jnp.zeros((nw,), acc)
        e = jnp.zeros((nv,), acc)
        k_run = kold.astype(acc)
        bad = jnp.asarray(False)
        iit = iiter
        tol_floor = jnp.maximum(floors.astype(acc), jnp.asarray(tol, acc))
        for _j in range(s):
            gamma = g0 @ cz - d @ (G.T @ cz)
            done = (k_run <= tol_floor) | (iit >= niter)
            acp = Amap @ cp
            delta = acp @ (G.T @ cp)
            alpha = gamma / delta
            sick = (~jnp.isfinite(alpha)) | (~jnp.isfinite(gamma)) \
                | (~jnp.isfinite(delta)) | (delta <= 0)
            bad = bad | (sick & ~done)
            live = ~done & ~bad
            alpha = jnp.where(live, alpha, 0.0)
            e = e + alpha * cp
            d = d + alpha * acp
            cz = cz - alpha * (Smap @ cp)
            gamma_n = g0 @ cz - d @ (G.T @ cz)
            beta = jnp.where(live, gamma_n / gamma, 0.0)
            cp = jnp.where(live, cz + beta * cp, cp)
            k_run = jnp.where(live, jnp.abs(gamma_n), k_run)
            iit = iit + jnp.where(live, 1, 0)
            cost = lax.dynamic_update_index_in_dim(
                cost, jnp.sqrt(k_run).astype(cost.dtype), iit, 0)
        # recombination — one local matvec against the stored basis
        def comb(base, coeff, mat):
            upd = (coeff @ mat).astype(base.dtype)
            return DistributedArray._wrap(base._arr + upd, base)

        xn = comb(x, e, Vm)
        rn = DistributedArray._wrap(
            r._arr - (d @ Wm[:nw]).astype(r.dtype), r)
        pn = DistributedArray._wrap((cp @ Vm).astype(r.dtype), r)
        zn = DistributedArray._wrap((cz @ Vm).astype(r.dtype), r)
        x = _reject(bad, x, xn)
        r = _reject(bad, r, rn)
        p = _reject(bad, p, pn)
        if precond:
            z = _reject(bad, z, zn)
        k = jnp.where(bad, kold, k_run.astype(kold.dtype))
        done_f = k <= jnp.maximum(floors, jnp.asarray(tol, kold.dtype))
        status, bestk, stall = _guard_update(
            status, bestk, stall, bad, k, done_f,
            stall_n if guards else _NO_STALL)
        telemetry.iteration("cg", iit, resid=jnp.sqrt(k), k=k,
                            alpha=jnp.asarray(0.0))
        head = (x, r, p, z) if precond else (x, r, p)
        return head + (k, iit, cost, status, bestk, stall)

    return body


def _sstep_cg_seed(Op, y, x0, *, niter, M):
    xdt = _vdtype(x0)
    x = x0  # donated
    r = y - Op.matvec(x)
    z = _precond_apply(M, r, xdt)
    kold = _rdot(r, z)
    floors = _mp_floor(kold)
    cost0 = jnp.zeros((niter + 1,) + jnp.shape(kold),
                      dtype=jnp.asarray(kold).dtype)
    cost0 = lax.dynamic_update_index_in_dim(cost0, jnp.sqrt(kold), 0, 0)
    head = (x, r, z, z) if M is not None else (x, r, r)
    return head, kold, floors, cost0


def _sstep_cg_fused(Op, y, x0, tol, *, niter, s, M=None, guards=False,
                    stall_n=0):
    """Whole s-step CA-CG solve as one ``lax.while_loop``; ALWAYS
    returns ``(x, iiter, cost, status)`` — the status word carries the
    basis-conditioning verdict the host fallback wrapper needs even on
    the unguarded path."""
    from ..resilience import status as _rstatus
    head, kold, floors, cost0 = _sstep_cg_seed(Op, y, x0, niter=niter,
                                               M=M)
    body = _make_sstep_body(Op, _vdtype(x0), floors, tol, s=s,
                            niter=niter, M=M, guards=guards,
                            stall_n=stall_n)
    nh = 4 if M is not None else 3
    state = head + (kold, jnp.asarray(0), cost0,
                    _i32(_rstatus.RUNNING), jnp.max(kold), _i32(0))

    def cond(st):
        return ((st[nh + 1] < niter) & (jnp.max(st[nh]) > tol)
                & (st[nh + 3] == _rstatus.RUNNING))

    out = lax.while_loop(cond, body, state)
    x, kold, iiter, cost, status = (out[0], out[nh], out[nh + 1],
                                    out[nh + 2], out[nh + 3])
    return x, iiter, cost, _resolve_status(status, kold, tol)


# ------------------------------------------------------ runners
def _guard_ctx(Op, guards):
    """(fault spec, stall window, extra key parts) for a guarded build
    — the same consume-once contract as the classic runners."""
    if not guards:
        return None, 0, ()
    from ..resilience import faults as _faults, status as _rstatus
    spec = _faults.consume()
    return spec, _rstatus.stall_window(), (
        _rstatus.guards_signature(True), _faults.fault_signature(spec))


def _call_pipe_cg(Op, y, x0, x0_owned, niter, tol, guards, M, *,
                  block=False, spec=None, stall_n=0, extra=()):
    name = "block_cg" if block else "cg"
    fn = _get_fused(Op, (id(Op), "ca-" + name, niter, _vkey(y),
                         _vkey(x0)) + extra + ca_key("pipelined")
                    + _mkey(M),
                    lambda op: partial(_pipe_cg_fused, op, niter=niter,
                                       guards=guards, M=M,
                                       stall_n=stall_n, fault=spec,
                                       block=block),
                    donate_argnums=_DONATE_X0, keepalive=M,
                    aot_eligible=(M is None and spec is None))
    out = fn(y, x0 if x0_owned else _donate_copy(x0), tol)
    if guards:
        x, iiter, cost, status = out
        return x, int(iiter), cost, status
    x, iiter, cost = out
    return x, int(iiter), cost, None


def run_cg_fused(Op, y, x0, x0_owned, niter, tol, guards, M=None,
                 mode="pipelined"):
    """CA twin of ``basic._run_cg_fused`` — same return contract
    ``(x, iiter, cost, status_code_or_None)``. ``mode="sstep"``
    downgrades to pipelined when the space is ineligible or a chaos
    fault is armed (faults inject at the classic per-iteration seams),
    and falls back to pipelined from the last completed outer iterate
    on a basis-conditioning breakdown."""
    from ..resilience import status as _rstatus
    from ..utils import deps as _deps
    spec, stall_n, extra = _guard_ctx(Op, guards)
    if mode == "sstep" and (spec is not None
                            or not _sstep_eligible(y, x0)):
        mode = "pipelined"
    if mode == "sstep":
        s = _deps.ca_s_default()
        fn = _get_fused(Op, (id(Op), "ca-cg", niter, _vkey(y),
                             _vkey(x0)) + extra + ca_key("sstep", s)
                        + _mkey(M),
                        lambda op: partial(_sstep_cg_fused, op,
                                           niter=niter, s=s, guards=guards,
                                           M=M, stall_n=stall_n),
                        donate_argnums=_DONATE_X0, keepalive=M,
                        aot_eligible=(M is None))
        x, iiter, cost, status = fn(
            y, x0 if x0_owned else _donate_copy(x0), tol)
        iiter, code = int(iiter), int(status)
        cost = np.asarray(cost)[:iiter + 1]
        if code == _rstatus.BREAKDOWN and iiter < niter:
            # monomial-basis conditioning guard fired: restart the
            # remaining budget on the s=1 (pipelined) engine from the
            # last completed outer iterate
            _record_fallback("cg", s, iiter)
            x, it2, cost2, status2 = _call_pipe_cg(
                Op, y, x, True, niter - iiter, tol, guards, M,
                stall_n=stall_n, extra=extra)
            cost = np.concatenate([cost, np.asarray(cost2)[1:it2 + 1]])
            iiter = iiter + it2
            code = int(status2) if status2 is not None else None
        elif not guards:
            code = None
    else:
        x, iiter, cost, status = _call_pipe_cg(
            Op, y, x0, x0_owned, niter, tol, guards, M, spec=spec,
            stall_n=stall_n, extra=extra)
        cost = np.asarray(cost)[:iiter + 1]
        code = int(status) if status is not None else None
    _metrics.inc("solver.cg.solves")
    _metrics.inc("solver.cg.iterations", iiter)
    if guards:
        _rstatus.record("cg", code, iiter)
        return x, iiter, cost, code
    return x, iiter, cost, None


def run_cgls_fused(Op, y, x0, x0_owned, niter, damp, tol, use_normal,
                   guards, M=None, mode="pipelined"):
    """CA twin of ``basic._run_cgls_fused`` — returns ``(x, iiter,
    cost, cost1, kold, status_code_or_None)``. Both CA modes solve the
    damped normal system, so ``cost``/``cost1`` carry the
    normal-residual norm ``sqrt(γ)``; ``sstep`` on the normal operator
    keeps the same breakdown→pipelined fallback as CG."""
    from ..resilience import status as _rstatus
    spec, stall_n, extra = _guard_ctx(Op, guards)
    # s-step CGLS would need the normal-operator chains; the pipelined
    # engine already collapses every CGLS dot into one reduction, so
    # sstep requests route there (docs/ca.md)
    fn = _get_fused(Op, (id(Op), "ca-cgls", use_normal, niter,
                         _vkey(y), _vkey(x0)) + extra
                    + ca_key("pipelined") + _mkey(M),
                    lambda op: partial(_pipe_cgls_fused, op, niter=niter,
                                       normal=use_normal, guards=guards,
                                       M=M, stall_n=stall_n, fault=spec),
                    donate_argnums=_DONATE_X0, keepalive=M,
                    aot_eligible=(M is None and spec is None))
    out = fn(y, x0 if x0_owned else _donate_copy(x0), damp, tol)
    if guards:
        x, iiter, cost, cost1, kold, status = out
        iiter, code = int(iiter), int(status)
    else:
        x, iiter, cost, cost1, kold = out
        iiter, code = int(iiter), None
    _count_cgls_solve(iiter, use_normal)
    if guards:
        _rstatus.record("cgls", code, iiter)
    return (x, iiter, np.asarray(cost)[:iiter + 1],
            np.asarray(cost1)[:iiter + 1], kold, code)


def run_block_cg(Op, y, x0, x0_owned, niter, tol, guards, M=None,
                 mode="pipelined"):
    """Pipelined block CG (K > 1): same public contract as the fused
    section of ``block.block_cg`` — ``(x, iiter, cost_np)`` with
    per-column status words recorded. s-step has no block variant
    (the Gram tile would grow with K); it pipelines."""
    from ..resilience import status as _rstatus
    spec, stall_n, extra = _guard_ctx(Op, guards)
    x, iiter, cost, status = _call_pipe_cg(
        Op, y, x0, x0_owned, niter, tol, guards, M, block=True,
        spec=spec, stall_n=stall_n, extra=extra)
    _metrics.inc("solver.block_cg.solves")
    _metrics.inc("solver.block_cg.iterations", iiter)
    if guards:
        _rstatus.record_columns(
            "block_cg", [int(cd) for cd in np.asarray(status)], iiter)
    return x, iiter, np.asarray(cost)[:iiter + 1]


def run_block_cgls(Op, y, x0, x0_owned, niter, damp, tol, guards,
                   M=None, mode="pipelined", use_normal=False):
    """Pipelined block CGLS (K > 1): public contract of
    ``block.block_cgls``'s fused section — ``(x, istop, iiter, kold,
    r2norm, cost)`` with the CA cost-lane caveat (normal-residual
    norms). ``use_normal`` is the schedule ``block_cgls`` resolved, as
    in :func:`run_cgls_fused`."""
    from ..resilience import status as _rstatus
    spec, stall_n, extra = _guard_ctx(Op, guards)
    fn = _get_fused(Op, (id(Op), "ca-block_cgls", use_normal, niter,
                         _vkey(y), _vkey(x0)) + extra
                    + ca_key("pipelined") + _mkey(M),
                    lambda op: partial(_pipe_cgls_fused, op, niter=niter,
                                       normal=use_normal, guards=guards,
                                       M=M, stall_n=stall_n, fault=spec,
                                       block=True),
                    donate_argnums=_DONATE_X0, keepalive=M,
                    aot_eligible=(M is None and spec is None))
    out = fn(y, x0 if x0_owned else _donate_copy(x0), damp, tol)
    if guards:
        x, iiter, cost, cost1, kold, status = out
        iiter = int(iiter)
        _rstatus.record_columns(
            "block_cgls", [int(cd) for cd in np.asarray(status)], iiter)
    else:
        x, iiter, cost, cost1, kold = out
        iiter = int(iiter)
    _count_cgls_solve(iiter, use_normal, "block_cgls")
    kold = np.asarray(kold)
    istop = np.where(kold < tol, 1, 2)
    return (x, istop, iiter, kold, np.asarray(cost1)[iiter],
            np.asarray(cost)[:iiter + 1])


# ------------------------------------------------------ segmented seams
def seg_fields(solver: str, mode: str, M) -> tuple:
    """Checkpoint field names of a CA segmented carry (the classic
    drivers' ``_CG_FIELDS`` analogue) — the pytree the epoch program
    threads and the checkpoint stores, keyed by engine and by the
    ``M=None`` carry elision."""
    if mode == "sstep":
        head = ("x", "r", "p", "z") if M is not None else ("x", "r", "p")
        return head + ("kold", "iiter", "cost", "status", "bestk",
                       "stall")
    if M is not None:
        head = ("x", "r", "u", "w", "z", "q", "s", "p")
    else:
        head = ("x", "r", "w", "z", "s", "p")
    return head + ("kold", "aold", "iiter", "cost", "status", "bestk",
                   "stall")


def check_resume_ca(state: dict, mode: str, s: Optional[int] = None):
    """Refuse a resume whose checkpoint was written under a different
    CA engine — the carries are different pytrees with different
    semantics. Pre-CA checkpoints carry no ``ca`` key and count as
    ``off``."""
    got = str(state.get("ca", "off"))
    want = mode
    if got != want:
        raise ValueError(
            f"fused-carry checkpoint was written with ca={got!r} but "
            f"this run requests ca={want!r}: resume must replay the "
            "same plan (set PYLOPS_MPI_TPU_CA to match or restart "
            "without resume=True)")
    if mode == "sstep":
        got_s = int(state.get("ca_s", 0))
        if s is not None and got_s != int(s):
            raise ValueError(
                f"fused-carry checkpoint was written with s={got_s} "
                f"but this run requests s={int(s)}: resume must replay "
                "the same plan")


def pipe_cg_setup_builder(Op, *, niter, M=None):
    """Segmented setup: returns the head vectors + ``(kold, aold,
    cost0, floors)`` — the driver seeds ``iiter``/status triple."""
    def setup(y, x0):
        head, kold, floors, aold, cost0 = _pipe_cg_seed(
            Op, y, x0, niter=niter, M=M, block=False)
        return head + (kold, aold, cost0, floors)

    return setup


def pipe_cgls_setup_builder(Op, *, niter, normal=False, M=None):
    def setup(y, x0, damp, damp2):
        head, kold, floors, aold, cost0, _ = _pipe_cgls_seed(
            Op, y, x0, damp, damp2, niter=niter, normal=normal, M=M,
            block=False)
        return head + (kold, aold, cost0, floors)

    return setup


def _pipe_epoch(applyA_of, fields_n, *, guards, stall_n, M, name):
    """Shared segmented epoch runner for the pipelined engines.
    ``applyA_of(damp2)`` binds the iterated operator (CG ignores the
    operand). Signature matches the classic epoch builders: ``run(y,
    *fields, floors[, damp2], tol, epoch_end)`` and returns the full
    field tuple (status triple always included — unguarded bodies
    thread the status word and pass ``bestk``/``stall`` through)."""
    from ..resilience import status as _rstatus
    precond = M is not None
    nh = 8 if precond else 6

    def run(y, *rest):
        vals = rest[:fields_n]
        tail = rest[fields_n:]
        if len(tail) == 4:
            floors, damp2, tol, epoch_end = tail
        else:
            floors, tol, epoch_end = tail
            damp2 = None
        xdt = _vdtype(vals[0])
        body = _make_pipe_body(applyA_of(damp2, xdt), xdt, floors, tol,
                               M=M, guards=guards,
                               carry_status=not guards,
                               stall_n=stall_n, name=name)
        if guards:
            def cond(st):
                return ((st[nh + 2] < epoch_end)
                        & (jnp.max(st[nh]) > tol)
                        & (st[nh + 4] == _rstatus.RUNNING))

            return lax.while_loop(cond, body, vals)

        def cond(st):
            return ((st[nh + 2] < epoch_end)
                    & (jnp.max(st[nh]) > tol)
                    & (st[nh + 4] == _rstatus.RUNNING))

        out = lax.while_loop(cond, body, vals[:-2])
        return out + tuple(vals[-2:])

    return run


def pipe_cg_epoch_builder(Op, *, guards, stall_n, M=None):
    n = len(seg_fields("cg", "pipelined", M))
    return _pipe_epoch(lambda damp2, xdt: Op.matvec, n, guards=guards,
                       stall_n=stall_n, M=M, name="cg")


def pipe_cgls_epoch_builder(Op, *, guards, stall_n, normal=False,
                            M=None):
    n = len(seg_fields("cgls", "pipelined", M))
    return _pipe_epoch(
        lambda damp2, xdt: _normal_apply(Op, damp2, xdt, normal), n,
        guards=guards, stall_n=stall_n, M=M, name="cgls")


def sstep_cg_setup_builder(Op, *, niter, M=None):
    def setup(y, x0):
        head, kold, floors, cost0 = _sstep_cg_seed(Op, y, x0,
                                                   niter=niter, M=M)
        return head + (kold, cost0, floors)

    return setup


def sstep_cg_epoch_builder(Op, *, s, niter, guards, stall_n, M=None):
    """Segmented s-step epochs: each outer body advances up to ``s``
    iterations, so an epoch may overshoot its boundary by at most
    ``s-1`` iterations (checkpoints land AT OR AFTER the requested
    boundary — the identity contract is per-carry, not per-boundary).
    A breakdown surfaces as ``status=BREAKDOWN`` and stops the driver;
    segmented runs do NOT auto-fall back (the caller restarts under
    ``PYLOPS_MPI_TPU_CA=pipelined``, which the mode-stamped carry then
    enforces)."""
    from ..resilience import status as _rstatus
    fields_n = len(seg_fields("cg", "sstep", M))
    nh = 4 if M is not None else 3

    def run(y, *rest):
        vals = rest[:fields_n]
        floors, tol, epoch_end = rest[fields_n:]
        body = _make_sstep_body(Op, _vdtype(vals[0]), floors, tol, s=s,
                                niter=niter, M=M, guards=guards,
                                stall_n=stall_n)

        def cond(st):
            return ((st[nh + 1] < epoch_end)
                    & (jnp.max(st[nh]) > tol)
                    & (st[nh + 3] == _rstatus.RUNNING))

        return lax.while_loop(cond, body, vals)

    return run
