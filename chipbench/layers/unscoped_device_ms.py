"""Solvers: device time an iteration of the leaf ops inside the
``cb.solve`` spans whose ``op_name`` holds no ``pmt.`` component at
all — what the compiler put between the program's parts: relayout
copies, the loop's plumbing (``solver_phases.own_split``; the five
largest by name on the log)."""
from chipbench import solver_phases


def read(ctx):
    return solver_phases.own(ctx, (solver_phases.UNSCOPED,))
