"""Benchmark client: how late the open-loop generator sent, against its
schedule (95th percentile) — a starved generator must not read as a
fast server."""
from chipbench import stats


def read(ctx):
    late = ctx["records"].get("late_s")
    if not late:
        return None
    p = stats.percentile(late, 95.0)
    return None if p is None else 1e3 * p
