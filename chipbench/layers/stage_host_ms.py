"""Host staging: median over the slice's batches of the time the
dispatcher's thread spent in its ``pmt.serve.pack``, ``stage_in``,
``pull`` and ``resolve`` spans."""
from chipbench import program_trace, stats


def read(ctx):
    batches = program_trace.stage_times(ctx)
    if not batches:
        return None
    split = {n: stats.median([b.get(n, 0.0) for b in batches.values()]) / 1e6
             for n in program_trace.STAGING + ("solve",)}
    ctx["log"](f"stage_host_ms: {len(batches)} batches, median ms a stage: "
               + ", ".join(f"{k} {v:.2f}" for k, v in split.items()))
    m = stats.median([sum(b.get(n, 0.0) for n in program_trace.STAGING)
                      for b in batches.values()])
    return None if m is None else m / 1e6
