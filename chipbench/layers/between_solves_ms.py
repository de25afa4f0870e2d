"""Host staging: median time from one solve's last device op to the
next solve's first (closed-loop cells), on the first device."""
from chipbench import stats


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    spans = t.per_span("solve", next(iter(t.devices)))
    gaps = [b[0] - a[1] for a, b in zip(spans, spans[1:])]
    m = stats.median(gaps)
    return None if m is None else m / 1e6
