"""Ops and kernels: device time an iteration of the leaf ops lowered
under ``pmt.MPIFredholm1.*`` — the complex batched product, forward and
adjoint, with whatever the compiler put inside its scopes (the joining
and splitting of the spectra's parts, relayouts). A program without the
scope has nothing to read."""
from chipbench import scope_time


def read(ctx):
    return scope_time.under(ctx, "pmt.MPIFredholm1.")
