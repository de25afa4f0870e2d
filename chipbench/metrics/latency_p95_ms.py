"""95th percentile on the same clock, only where ten samples lie beyond
it; a missing answer is infinitely late."""
from chipbench import stats


def read(ctx):
    lat = ctx["records"]["latencies_s"]
    p = stats.percentile(lat, 95.0)
    ctx["log"](f"latency_p95_ms: {len(lat)} samples, "
               f"{'reported' if p is not None else 'too few, left out'}")
    return None if p is None else 1e3 * p
