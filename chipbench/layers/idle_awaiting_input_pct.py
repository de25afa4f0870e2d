"""Host staging: share of the slice in which the device is idle
between the end of ``pmt.solver.launch`` and the program's first op —
dispatched, and the program has not begun: it waits for its operands
(``solver_phases.host_phases``)."""
from chipbench import solver_phases


def read(ctx):
    return solver_phases.idle_share(ctx, "input")
