"""Differentiable operator layer (ROADMAP item 5).

The reference library is solve-only; this tier makes the whole stack
end-to-end differentiable without ever asking JAX to transpose a
shard_map collective or unroll a ``lax.while_loop`` tape:

- :mod:`rules` — adjoint-based ``jax.custom_vjp``/``custom_jvp`` rules
  for operator applies: the VJP of ``A @ x`` w.r.t. ``x`` is ``Aᴴ @ v``,
  which every ``MPILinearOperator`` already carries as ``rmatvec``.
  Parameter cotangents (MatrixMult weights, sparse COO vals, precond
  diagonals) flow through the existing pytree registration.
- :mod:`implicit` — implicit differentiation through the fused
  CG/CGLS fixed points (and their block ``(N, K)`` carries): the
  backward pass is ONE more solve with the same operator family,
  reusing the ``_get_fused`` executables, tuned plans, CA mode, the
  ``M=`` preconditioner seam and the AOT bank.
- :mod:`unrolled` — reverse-differentiable fixed-iteration (scan-tape)
  CG/CGLS oracles, used by the tests as the "what everyone else
  does" baseline.
- :mod:`fit` — a minimal ``value_and_grad`` training driver
  (grad-of-``batched_solve`` over an operator family = minibatch
  training of a learned regularizer).

``PYLOPS_MPI_TPU_AUTODIFF=on`` additionally lets the CLASSIC entries
(``cg``/``cgls``/``block_cg``/``block_cgls``) accept traced inputs and
route here; the explicit API below works with the knob off too, and
off-mode lowers bit-identical solver programs (tests/test_autodiff.py).
See docs/autodiff.md for rule semantics and the guard exclusion.
"""

from .rules import (DifferentiableOperator, make_differentiable)
from .implicit import (cg_solve, cgls_solve, block_cg_solve,
                       block_cgls_solve)
from .unrolled import unrolled_cg, unrolled_cgls
from .fit import fit, trainable_leaves, param_count
from . import rules, implicit, unrolled  # noqa: F401  (submodule access)
from . import fit as _fit_mod  # noqa: F401

__all__ = [
    "DifferentiableOperator", "make_differentiable",
    "cg_solve", "cgls_solve", "block_cg_solve", "block_cgls_solve",
    "unrolled_cg", "unrolled_cgls",
    "fit", "trainable_leaves", "param_count",
]
