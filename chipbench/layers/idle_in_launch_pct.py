"""Host staging: share of the slice in which the device is idle
between the start of a ``pmt.serve.solve`` span and the end of the
``pmt.solver.launch`` inside it — the dispatcher's thread prepares and
dispatches the solve (``solver_phases.host_phases``)."""
from chipbench import solver_phases


def read(ctx):
    return solver_phases.idle_share(ctx, "launch")
