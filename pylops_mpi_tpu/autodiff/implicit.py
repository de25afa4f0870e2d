"""Implicit differentiation through the fused solves.

``lax.while_loop`` is not reverse-differentiable, and even if it were,
an unrolled tape would hold every iterate (O(niter · n) memory). A
converged Krylov solve does not need either: differentiate the FIXED
POINT instead of the iteration.

CG (SPD ``A``), fixed point ``A x* = y``::

    dA x* + A dx* = dy
    ⟨v, dx*⟩ = ⟨λ, dy⟩ − ⟨λ, dA x*⟩          with  Aᵀ λ = v

so the backward pass is ONE more solve with the same operator
(``∂y = λ``; parameter cotangents are the pullback of ``θ ↦ A(θ) x*``
at ``λ``, negated — see :func:`rules.param_cotangent`).

CGLS (damped least squares), fixed point
``N x* = Aᴴ y`` with ``N = AᴴA + damp²``::

    ⟨v, dx*⟩ = ⟨μ, dy⟩ + ⟨λ, dAᴴ r*⟩ − ⟨μ, dA x*⟩
    with  Nᵀ λ = v,  μ = (Aᴴ)ᵀ λ,  r* = y − A x*

— one CG solve on the normal operator (the same system CGLS itself
iterates on, so the ``M=`` preconditioner seam transfers unchanged).

The backward solve dispatches exactly like the forward one: concrete
inputs run the cached host path (``_run_*_fused`` — same ``_get_fused``
executables, tuned plans, CA engines, AOT bank as plain solves; a
gradient costs one forward-shaped solve), traced inputs (under
``jax.jit``/nested transforms) inline the fused builders into the
surrounding trace. Guards are EXCLUDED from the rule: the fixed-point
algebra differentiates the converged iterate, not the in-loop
breakdown ``select`` machinery, so the traced path always uses the
unguarded builders (docs/autodiff.md). The preconditioner ``M`` and
the cost/iteration diagnostics are gradient-transparent: ``M`` changes
the iteration, not the fixed point, and the diagnostic outputs carry
``stop_gradient`` semantics (their cotangents are discarded).
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["cg_solve", "cgls_solve", "block_cg_solve",
           "block_cgls_solve", "should_intercept"]


# ------------------------------------------------------------ helpers
def _leaves(*pytrees):
    for t in pytrees:
        if t is None:
            continue
        yield from jax.tree_util.tree_leaves(t)


def _has_tracer(*pytrees) -> bool:
    return any(isinstance(l, jax.core.Tracer) for l in _leaves(*pytrees))


def should_intercept(Op, y, x0=None) -> bool:
    """True when a classic solver entry holds traced inputs that the
    host path cannot run (``int(iiter)`` on a tracer) — the
    ``PYLOPS_MPI_TPU_AUTODIFF=on`` reroute predicate. Concrete solves
    never intercept: off-mode and on-mode lower identical programs."""
    return _has_tracer(Op, y, x0)


def _zeros_like_vec(v):
    return jax.tree_util.tree_map(jnp.zeros_like, v)


def _conj_if_complex(v):
    if np.issubdtype(np.dtype(v.dtype), np.complexfloating):
        return v.conj()
    return v


class _NormalOperator:
    """``v ↦ AᴴA v + damp² v`` — the model-space normal system the
    CGLS backward pass solves. Closure-only (never a pytree leaf);
    block inputs route through the sub-operator's public applies."""

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


# Concrete backward solves build the normal operator once per
# (operator, damp) so repeated gradient steps reuse ONE fused-cache
# entry instead of recompiling per call (id(Nop) keys the cache).
_NORMAL_MEMO: OrderedDict = OrderedDict()
_NORMAL_MEMO_MAX = 16


def _normal_operator(Op, damp: float):
    if _has_tracer(Op):
        return _NormalOperator(Op, damp)
    key = (id(Op), float(damp))
    hit = _NORMAL_MEMO.get(key)
    if hit is not None and hit[0] is Op:
        _NORMAL_MEMO.move_to_end(key)
        return hit[1]
    Nop = _NormalOperator(Op, damp)
    _NORMAL_MEMO[key] = (Op, Nop)
    while len(_NORMAL_MEMO) > _NORMAL_MEMO_MAX:
        _NORMAL_MEMO.popitem(last=False)
    return Nop


# ------------------------------------------------------ forward passes
def _forward_cg(Op, y, x0, niter, tol, M, block):
    """One fused CG solve → ``(x, iiter, cost)``. Concrete inputs run
    the cached host path (same executables as plain ``cg``); traced
    inputs inline the unguarded fused builder."""
    from ..solvers import basic as _b
    if not _has_tracer(Op, y, x0):
        if block:
            from ..solvers import block as _blk
            x, iiter, cost = _blk.block_cg(Op, y, x0, niter=niter,
                                           tol=tol, guards=False, M=M)
            return x, iiter, cost
        x, iiter, cost, _ = _b._run_cg_fused(Op, y, x0, False, niter,
                                             tol, False, M=M)
        return x, iiter, cost
    from ..solvers import ca as _ca
    mode = _ca.resolve_mode(Op, "cg")
    if mode != "off":
        # s-step's host-side breakdown fallback cannot run under trace;
        # the pipelined twin covers both CA modes here
        return _ca._pipe_cg_fused(Op, y, x0, tol, niter=niter, M=M,
                                  block=block)
    if block:
        from ..solvers import block as _blk
        return _blk._block_cg_fused(Op, y, x0, tol, niter=niter, M=M)
    return _b._cg_fused(Op, y, x0, tol, niter=niter, M=M)


def _forward_cgls(Op, y, x0, niter, damp, tol, M, block):
    """One fused CGLS solve → ``(x, iiter, cost, cost1, kold)``."""
    from ..solvers import basic as _b
    if not _has_tracer(Op, y, x0):
        if block:
            from ..solvers import block as _blk
            # classic schedule, like the single-RHS forward below: the
            # key says so, shared with ``block_cgls(normal=False)``
            return _blk._run_block_cgls_fused(Op, y, x0, niter, damp,
                                              tol, M, use_normal=False)
        x, iiter, cost, cost1, kold, _, _ = _b._run_cgls_fused(
            Op, y, x0, False, niter, damp, tol, False, False, M=M)
        return x, iiter, cost, cost1, kold
    from ..solvers import ca as _ca
    mode = _ca.resolve_mode(Op, "cgls")
    if mode != "off":
        return _ca._pipe_cgls_fused(Op, y, x0, damp, tol, niter=niter,
                                    M=M, block=block)
    if block:
        from ..solvers import block as _blk
        return _blk._block_cgls_fused(Op, y, x0, damp, tol,
                                      niter=niter, M=M)
    return _b._cgls_fused(Op, y, x0, damp, tol, niter=niter, M=M)


# ----------------------------------------------------- backward passes
def _cg_backward(Op, xstar, v, niter, tol, M, block, want_params):
    """``Aᵀ λ = v`` by one more CG solve (SPD: same operator, so the
    tuned plans / CA engine / AOT entry of the forward family are the
    ones that run); cotangents ``(gy, gleaves)`` — the operator
    cotangent as a flat LEAF LIST in ``tree_flatten(Op)`` order (see
    rules.py on why operator-shaped cotangent pytrees cannot pass
    custom_vjp's structure check)."""
    from ..diagnostics import metrics as _metrics
    _metrics.inc("autodiff.backward_solves")
    vc = _conj_if_complex(v)
    lam = _forward_cg(Op, vc, _zeros_like_vec(vc), niter, tol, M,
                      block)[0]
    lam = _conj_if_complex(lam)
    gy = lam
    gleaves = None
    if want_params:
        from .rules import param_cotangent
        gop = param_cotangent(Op, xstar, lam)
        gleaves = [_neg_leaf(l) for l in
                   jax.tree_util.tree_leaves(gop)]
    return gy, gleaves


def _cgls_backward(Op, y, xstar, v, niter, damp, tol, M, block,
                   want_params):
    """``Nᵀ λ = v`` (N the damped normal operator) by one CG solve,
    then ``μ = (Aᴴ)ᵀ λ``; cotangents ``(gy, gleaves)`` (leaf-list
    operator cotangent, see :func:`_cg_backward`)."""
    from ..diagnostics import metrics as _metrics
    from .rules import transpose_apply, param_cotangent
    _metrics.inc("autodiff.backward_solves")
    Nop = _normal_operator(Op, damp)
    vc = _conj_if_complex(v)
    lam = _forward_cg(Nop, vc, _zeros_like_vec(vc), niter, tol, M,
                      block)[0]
    lam = _conj_if_complex(lam)
    mu = transpose_apply(Op, lam, "rmatvec")
    gy = mu
    gleaves = None
    if want_params:
        rstar = y - Op.matvec(xstar)
        t1 = param_cotangent(Op, rstar, lam, "rmatvec")
        t2 = param_cotangent(Op, xstar, mu, "matvec")
        gleaves = [_sub_leaf(a, b) for a, b in
                   zip(jax.tree_util.tree_leaves(t1),
                       jax.tree_util.tree_leaves(t2))]
    return gy, gleaves


def _neg_leaf(a):
    return a if _is_float0(a) else -a


def _sub_leaf(a, b):
    return a if _is_float0(a) else a - b


def _is_float0(a) -> bool:
    return getattr(a, "dtype", None) == jax.dtypes.float0


# ----------------------------------------------------- custom_vjp glue
def _op_from_leaves(Op_orig, leaves, treedef):
    """Rebuild the operator from the rule's leaf-list argument —
    UNLESS both the leaves and the original operator are concrete, in
    which case the leaves are the ones just flattened off ``Op_orig``
    and returning the original instance preserves the ``id(Op)``-keyed
    fused-cache/AOT entries (an unflattened copy would recompile every
    gradient step). When ``Op_orig`` was built inside a transform (its
    leaves are tracers of the OUTER trace — e.g. ``grad`` w.r.t.
    operator parameters) it must NOT be reused with the concrete
    primal leaves custom_vjp hands the fwd/bwd passes: that would leak
    the outer tracers into the rule's pure-primal computation."""
    if not _has_tracer(leaves) and not _has_tracer(Op_orig):
        return Op_orig
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _make_cg_rule(niter, tol, M, block, treedef=None, Op_orig=None,
                  Op_static=None):
    if treedef is not None:
        @jax.custom_vjp
        def solve(leaves, y, x0):
            op = _op_from_leaves(Op_orig, leaves, treedef)
            return _forward_cg(op, y, x0, niter, tol, M, block)

        def fwd(leaves, y, x0):
            op = _op_from_leaves(Op_orig, leaves, treedef)
            outs = _forward_cg(op, y, x0, niter, tol, M, block)
            return outs, (leaves, outs[0])

        def bwd(res, cts):
            leaves, xstar = res
            op = _op_from_leaves(Op_orig, leaves, treedef)
            gy, gleaves = _cg_backward(op, xstar, cts[0], niter, tol,
                                       M, block, want_params=True)
            return gleaves, gy, _zeros_like_vec(xstar)

        solve.defvjp(fwd, bwd)
        return solve

    @jax.custom_vjp
    def solve(y, x0):
        return _forward_cg(Op_static, y, x0, niter, tol, M, block)

    def fwd(y, x0):
        outs = _forward_cg(Op_static, y, x0, niter, tol, M, block)
        return outs, outs[0]

    def bwd(xstar, cts):
        gy, _ = _cg_backward(Op_static, xstar, cts[0], niter, tol, M,
                             block, want_params=False)
        return gy, _zeros_like_vec(xstar)

    solve.defvjp(fwd, bwd)
    return solve


def _make_cgls_rule(niter, damp, tol, M, block, treedef=None,
                    Op_orig=None, Op_static=None):
    if treedef is not None:
        @jax.custom_vjp
        def solve(leaves, y, x0):
            op = _op_from_leaves(Op_orig, leaves, treedef)
            return _forward_cgls(op, y, x0, niter, damp, tol, M, block)

        def fwd(leaves, y, x0):
            op = _op_from_leaves(Op_orig, leaves, treedef)
            outs = _forward_cgls(op, y, x0, niter, damp, tol, M, block)
            return outs, (leaves, y, outs[0])

        def bwd(res, cts):
            leaves, y, xstar = res
            op = _op_from_leaves(Op_orig, leaves, treedef)
            gy, gleaves = _cgls_backward(op, y, xstar, cts[0], niter,
                                         damp, tol, M, block,
                                         want_params=True)
            return gleaves, gy, _zeros_like_vec(xstar)

        solve.defvjp(fwd, bwd)
        return solve

    @jax.custom_vjp
    def solve(y, x0):
        return _forward_cgls(Op_static, y, x0, niter, damp, tol, M,
                             block)

    def fwd(y, x0):
        outs = _forward_cgls(Op_static, y, x0, niter, damp, tol, M,
                             block)
        return outs, (y, outs[0])

    def bwd(res, cts):
        y, xstar = res
        gy, _ = _cgls_backward(Op_static, y, xstar, cts[0], niter,
                               damp, tol, M, block, want_params=False)
        return gy, _zeros_like_vec(xstar)

    solve.defvjp(fwd, bwd)
    return solve


def _solve_cg(Op, y, x0, niter, tol, M, block):
    from ..linearoperator import operator_is_jit_arg
    if x0 is None:
        x0 = _default_x0(Op, y, block)
    if operator_is_jit_arg(Op):
        leaves, treedef = jax.tree_util.tree_flatten(Op)
        rule = _make_cg_rule(niter, tol, M, block, treedef=treedef,
                             Op_orig=Op)
        return rule(leaves, y, x0)
    rule = _make_cg_rule(niter, tol, M, block, Op_static=Op)
    return rule(y, x0)


def _solve_cgls(Op, y, x0, niter, damp, tol, M, block):
    from ..linearoperator import operator_is_jit_arg
    if x0 is None:
        x0 = _default_x0(Op, y, block)
    if operator_is_jit_arg(Op):
        leaves, treedef = jax.tree_util.tree_flatten(Op)
        rule = _make_cgls_rule(niter, damp, tol, M, block,
                               treedef=treedef, Op_orig=Op)
        return rule(leaves, y, x0)
    rule = _make_cgls_rule(niter, damp, tol, M, block, Op_static=Op)
    return rule(y, x0)


def _default_x0(Op, y, block):
    # global shape / mesh / partition are static even when y is traced,
    # so the zero model is a concrete constant of the trace
    if block:
        from ..solvers.block import _zero_block_model
        return _zero_block_model(Op, y)
    from ..solvers.basic import _zero_like_model
    return _zero_like_model(Op, y)


# ------------------------------------------------------------ user API
def cg_solve(Op, y, x0=None, *, niter: int = 10, tol: float = 1e-4,
             M=None):
    """Differentiable fused CG: returns ``x`` only, with the implicit
    fixed-point VJP installed (backward pass = one more CG solve with
    the same operator/preconditioner family). Works with
    ``PYLOPS_MPI_TPU_AUTODIFF`` off — the knob only gates the CLASSIC
    entries' tracer reroute. Gradients flow to ``y``, and to ``Op``'s
    pytree leaves when the operator is jit-argument clean; ``x0``
    receives zero cotangent (the converged iterate does not depend on
    the start), ``M`` and the diagnostics are gradient-transparent."""
    return _solve_cg(Op, y, x0, niter, tol, M, block=False)[0]


def cgls_solve(Op, y, x0=None, *, niter: int = 10, damp: float = 0.0,
               tol: float = 1e-4, M=None):
    """Differentiable fused CGLS: returns ``x`` only; backward pass is
    one CG solve on the damped normal operator ``AᴴA + damp²`` (the
    system CGLS itself iterates on, so ``M=`` transfers). See
    :func:`cg_solve` for the cotangent contract."""
    return _solve_cgls(Op, y, x0, niter, damp, tol, M, block=False)[0]


def block_cg_solve(Op, y, x0=None, *, niter: int = 10,
                   tol: float = 1e-4, M=None):
    """Differentiable fused block CG over an ``(n, K)`` carry — the
    fixed-point rule applies column-wise; one block backward solve
    covers all K cotangent columns."""
    return _solve_cg(Op, y, x0, niter, tol, M, block=True)[0]


def block_cgls_solve(Op, y, x0=None, *, niter: int = 10,
                     damp: float = 0.0, tol: float = 1e-4, M=None):
    """Differentiable fused block CGLS over an ``(n, K)`` carry; see
    :func:`block_cg_solve` / :func:`cgls_solve`."""
    return _solve_cgls(Op, y, x0, niter, damp, tol, M, block=True)[0]


# ------------------------------------------------- classic-entry shims
# The PYLOPS_MPI_TPU_AUTODIFF=on reroute targets: same return contracts
# as the host entries, but every host-only conversion (int(iiter),
# np.asarray slicing, istop comparison) becomes its traced equivalent.
def entry_cg(Op, y, x0, niter, tol, M):
    x, iiter, cost = _solve_cg(Op, y, x0, niter, tol, M, block=False)
    return x, iiter, cost


def entry_cgls(Op, y, x0, niter, damp, tol, M):
    x, iiter, cost, cost1, kold = _solve_cgls(Op, y, x0, niter, damp,
                                              tol, M, block=False)
    istop = jnp.where(jnp.max(kold) < tol, 1, 2)
    return x, istop, iiter, kold, jnp.take(cost1, iiter), cost


def entry_block_cg(Op, y, x0, niter, tol, M):
    return _solve_cg(Op, y, x0, niter, tol, M, block=True)


def entry_block_cgls(Op, y, x0, niter, damp, tol, M):
    x, iiter, cost, cost1, kold = _solve_cgls(Op, y, x0, niter, damp,
                                              tol, M, block=True)
    istop = jnp.where(jnp.max(kold) < tol, 1, 2)
    return x, istop, iiter, kold, jnp.take(cost1, iiter, axis=0), cost
