"""Collectives: of the time a collective was in flight, the share
during which no other op ran on that device."""
from chipbench.layers import collective_ms_per_iter


def read(ctx):
    got = collective_ms_per_iter.in_flight(ctx)
    return None if got is None else 100.0 * got[1] / got[0]
