"""Host staging: share of the slice in which the device is idle while
the dispatcher's thread is inside ``pmt.serve.pack``, ``stage_in``,
``pull`` or ``resolve`` — the part of ``device_idle_pct`` the host
causes."""
from chipbench import program_trace


def read(ctx):
    split = program_trace.idle_split(ctx)
    return None if split is None else split["staging"]
