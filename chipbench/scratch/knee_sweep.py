#!/usr/bin/env python3
"""Find the open-loop knee of a served cell, once, on the chip.

    python3 chipbench/scratch/knee_sweep.py --workload <cell> \
        --rates 12,16,20,24 --seconds 12 [--seed 0] [--rehearse]

Builds the cell's deployment once, then drives its traffic loop at each
offered rate in turn (same loop, same files as ``run.py``; only
``rate_per_s`` is overridden) and prints one line a rate: offered rate,
answers in the window, p50/p95 on the due-time clock, the backlog at
the window's end. The knee is the highest rate whose backlog does not
grow and whose answers keep up with the arrivals; the cell's traffic
file then carries 0.8 of it as a number. Not part of the measured
benchmark: a scratch tool, run by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import run as R, stats
    _, cell, cfg, traffic, sizes = R.open_cell(args.workload, args.rehearse)
    _, mesh, compiles, _ = R.attach(args.workload, int(cell["chips"]),
                                    args.rehearse)
    dep = R.find("builders", cfg["builder"]).build(
        cfg, sizes, args.seed, mesh, R.log)
    loop = R.find("loops", traffic["loop"]).run
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        h = R.Handle(compiles, float(cfg["guarantees"]["rel_tol"]),
                     False, 0.0, None)
        rec = loop(dep, dict(traffic, rate_per_s=rate), args.seconds,
                   args.seed, h)
        lat = rec["latencies_s"]
        row = {
            "offered_per_s": rate, "attempted": rec["attempted"],
            "failed": rec["failed"],
            "answered_in_window": rec["answered_in_window"],
            "backlog_at_end": rec["attempted"] - rec["answered_in_window"],
            "p50_ms": 1e3 * stats.median(lat),
            "p95_ms": 1e3 * (stats.percentile(lat, 95.0, beyond=3) or 0),
            "max_ms": 1e3 * max(lat),
            "batches": rec["service"]["batches"],
            "fill_mean": rec["service"]["fill_mean"],
            "late_p95_ms": 1e3 * (stats.percentile(rec["late_s"], 95.0,
                                                   beyond=3) or 0),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"knee_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
