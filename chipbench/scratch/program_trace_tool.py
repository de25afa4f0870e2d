#!/usr/bin/env python3
"""Hand-run: look at the program's side of a traced run.

    python3 chipbench/scratch/program_trace_tool.py dump <cell>
    python3 chipbench/scratch/program_trace_tool.py read <cell>

``dump`` prints, for the newest trace of ``<cell>`` under
``chipbench/out/trace``, every plane and line with its event count and
the first events of each distinct name with their own and their
metadata's stats: where a trace carries what (how ``program_trace``'s
scope carrier was found). ``read`` runs the cell's per-layer readers
again on that trace and on the records ``run.py`` left in
``chipbench/out/<cell>.seed*.trace1.json`` — no chip, no JAX device.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import program_trace as P, trace_reduce as T  # noqa: E402


def dump(cell: str, per_line: int = 6) -> None:
    path = T.newest_xplane(os.path.join(P.OUT, "trace", cell))
    print(path, os.path.getsize(path), "bytes")
    for plane in P.planes(path):
        print("PLANE", plane.name)
        for idx, name, events in plane.lines():
            print(f"  LINE {idx} {name!r}: {len(events)} events")
            seen = set()
            for s, e, ename, own, meta in events:
                key = ename.split(" = ")[0]
                if key in seen or (len(seen) >= per_line
                                   and not ename.startswith(P.PREFIX)):
                    continue
                seen.add(key)
                print(f"    {ename[:90]!r} {s:.0f}+{e - s:.0f}")
                print("      own ", str(plane.stats(own))[:700])
                print("      meta", str(plane.stats(meta))[:700])


def read(cell: str) -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    detail_path = sorted(glob.glob(os.path.join(
        P.OUT, cell + ".seed*.trace1.json")), key=os.path.getmtime)[-1]
    with open(detail_path) as f:
        detail = json.load(f)
    ctx = {"records": detail["records"], "cell": {"name": cell},
           "trace": T.load(T.newest_xplane(
               os.path.join(P.OUT, "trace", cell))),
           "log": lambda m: print("[log]", m)}
    for m in bench["per_layer"]:
        if m["source"] in ("device_trace", "program_span") \
                and cell in m.get("workloads", [cell]):
            try:
                v = importlib.import_module(
                    "chipbench.layers." + m["name"]).read(ctx)
            except KeyError as exc:      # a reader that needs run.py's ctx
                v = f"needs {exc}"
            print(m["name"], v, "was",
                  detail["result"]["metrics"].get(m["name"]))


if __name__ == "__main__":
    {"dump": dump, "read": read}[sys.argv[1]](sys.argv[2])
