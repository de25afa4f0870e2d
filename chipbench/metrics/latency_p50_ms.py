"""Median time of one solve as its caller sees it (closed loop: call to
``block_until_ready``; open loop: due instant to answer in hand)."""
from chipbench import stats


def read(ctx):
    m = stats.median(ctx["records"]["latencies_s"])
    return None if m is None else 1e3 * m
