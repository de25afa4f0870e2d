"""Ops and kernels: device time an iteration of the leaf ops lowered
under ``pmt.local.FFT`` — the four real FFT passes of an iteration
(model and data side, each way) with the shifts, scalings and relayouts
that sit in their scopes; the frequency slice and the zero pad are
``local.Identity``'s and land outside. A program without the scope has
nothing to read."""
from chipbench import scope_time


def read(ctx):
    return scope_time.under(ctx, "pmt.local.FFT")
