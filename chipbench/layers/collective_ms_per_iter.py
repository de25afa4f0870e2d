"""Collectives: time a collective XLA names (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) was in flight inside
the ``solve`` spans, per iteration, mean over the devices."""


def in_flight(ctx):
    """``(in flight ns, exposed ns, iterations)`` summed over devices."""
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    niter = ctx["records"]["iterations_per_solve"]
    fly = exp = its = 0.0
    for d in t.devices:
        for s, e in t.span_list("solve"):
            a, b = t.collective_exposed(d, s, e)
            fly, exp, its = fly + a, exp + b, its + niter
    return (fly, exp, its) if fly > 0 else None


def read(ctx):
    got = in_flight(ctx)
    return None if got is None else got[0] / got[2] / 1e6
