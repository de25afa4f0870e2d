"""Solvers: device-busy time inside the benchmark's ``solve`` spans over
the iterations run in them, mean over the devices."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    niter = ctx["records"]["iterations_per_solve"]
    per_dev = []
    for d in t.devices:
        spans = t.per_span("solve", d)
        if spans:
            per_dev.append(sum(b for _, _, b in spans)
                           / (len(spans) * niter))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / 1e6
