"""Solvers: device time an iteration of the leaf ops whose innermost
program scope is ``pmt.solver.cost`` — the two norms only the cost
histories read, and the histories' updates
(``solver_phases.own_split``). A fusion carries one name: an update
fused with such a norm is counted here."""
from chipbench import solver_phases


def read(ctx):
    return solver_phases.own(ctx, (solver_phases.COST,))
