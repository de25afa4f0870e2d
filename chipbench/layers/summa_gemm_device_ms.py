"""Ops and kernels: device time per iteration of the leaf ops whose
innermost program scope is ``pmt.summa.gemm`` — SUMMA's local GEMMs,
apart from the hops, resharding and padding around them. A program
without that scope (or a cell without SUMMA) has nothing to read."""
from chipbench import program_trace

SCOPE = "pmt.summa.gemm"


def read(ctx):
    split = program_trace.operator_split(ctx)
    return None if not split or SCOPE not in split else split[SCOPE]
