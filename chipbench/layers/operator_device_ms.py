"""Ops and kernels: device time per iteration of the leaf ops lowered
under a ``pmt.`` operator scope, inside the benchmark's ``solve``
spans."""
from chipbench import program_trace


def read(ctx):
    split = program_trace.operator_split(ctx)
    return None if not split else sum(split.values())
