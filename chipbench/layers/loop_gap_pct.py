"""Solvers: idle share INSIDE the solves — between a solve's first and
last device op, the part in which no op ran (gaps between the ops of
the ``while_loop`` body), on the idlest device."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    worst = None
    for d in t.devices:
        spans = t.per_span("solve", d)
        length = sum(e - s for s, e, _ in spans)
        if length > 0:
            gap = 1.0 - sum(b for _, _, b in spans) / length
            worst = gap if worst is None else max(worst, gap)
    return None if worst is None else 100.0 * worst
