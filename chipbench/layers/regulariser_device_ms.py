"""Ops and kernels: device time an iteration of the leaf ops lowered
under ``pmt.MPILaplacian.*`` — the regulariser's stencils (its scaling
lies outside that scope). A program without the scope has nothing to
read."""
from chipbench import scope_time


def read(ctx):
    return scope_time.under(ctx, "pmt.MPILaplacian.")
