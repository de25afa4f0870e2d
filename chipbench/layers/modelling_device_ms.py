"""Ops and kernels: device time an iteration of the leaf ops lowered
under ``pmt.MPIBlockDiag.*`` — the modelling operator, its local
convolution and derivative included. A program without the scope has
nothing to read."""
from chipbench import scope_time


def read(ctx):
    return scope_time.under(ctx, "pmt.MPIBlockDiag.")
