#!/usr/bin/env python3
"""How far do the due instants alone decide an open-loop cell's tail?

    python3 chipbench/scratch/queue_sim.py --seconds 50 --seeds 51,52,53

A plain simulation of the service's batch queue, fed nothing but the
due instants ``stats.arrivals`` draws for each seed: a batch forms once
its oldest request has waited ``--window`` seconds or ``--kmax`` wait,
takes up to ``--kmax`` requests, is padded to the service's buckets and
holds the server ``--t1`` (bucket 1) to ``--tk`` (the largest bucket)
seconds. It prints each seed's simulated p95 on the due-time clock. In
PR 22 these correlated 0.93 with the p95 measured on the chip over six
seeds at 50 s and named both outliers, which is why ``serve_open``
fixes its instants (``arrival_seed``). A scratch tool run by hand: no
JAX, no chip, never a device number.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

BUCKETS = (1, 2, 4, 8, 16)


def latencies(due, window, kmax, t1, tk):
    """Due-time latency of every request, the server starting idle."""
    out, free, i = [], 0.0, 0
    while i < len(due):
        start = max(free, due[i] + window)
        j = i
        while j < len(due) and j - i < kmax and due[j] <= start:
            j += 1
        if j - i == kmax:                    # full: goes without the window
            start = max(free, due[j - 1])
        bucket = next(b for b in BUCKETS if b >= j - i)
        free = start + t1 + (tk - t1) * (bucket - 1) / (BUCKETS[-1] - 1)
        out += [free - d for d in due[i:j]]
        i = j
    return out


def main(argv=None) -> int:
    from chipbench import stats
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rate", type=float, default=14.4)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--window", type=float, default=0.010)
    ap.add_argument("--kmax", type=int, default=16)
    ap.add_argument("--t1", type=float, default=0.80)
    ap.add_argument("--tk", type=float, default=0.92)
    a = ap.parse_args(argv)
    for seed in (int(s) for s in a.seeds.split(",")):
        lat = latencies(stats.arrivals(a.rate, a.seconds, seed), a.window,
                        a.kmax, a.t1, a.tk)
        print(seed, "p95_ms %.1f" % (1e3 * stats.percentile(lat, 95.0)),
              "p50_ms %.1f" % (1e3 * stats.median(lat)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
