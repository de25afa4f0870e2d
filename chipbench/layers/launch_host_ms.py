"""Host staging: median over the slice's solves of the wrapper's
``pmt.solver.launch`` span — from the first line of the fused
wrapper to the return of the program's asynchronous call
(``solver_phases.host_phases``)."""
from chipbench import solver_phases


def read(ctx):
    found = solver_phases.host_phases(ctx)
    return None if found is None else found["launch_ms"]
