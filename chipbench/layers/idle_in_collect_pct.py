"""Service: share of the slice in which the device is idle while the
dispatcher's thread is inside ``pmt.serve.collect`` — no demand, or the
batching window."""
from chipbench import program_trace


def read(ctx):
    split = program_trace.idle_split(ctx)
    return None if split is None else split["collect"]
