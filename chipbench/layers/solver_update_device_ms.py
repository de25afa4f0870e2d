"""Solvers: device time an iteration of the leaf ops whose innermost
program scope is ``pmt.solver.setup``, ``step`` or ``direction`` — the
recurrence's own vector updates with the reductions they feed, apart
from every operator apply (``solver_phases.own_split``)."""
from chipbench import solver_phases


def read(ctx):
    return solver_phases.own(ctx, solver_phases.UPDATE)
