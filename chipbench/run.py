#!/usr/bin/env python3
"""chipbench/run.py — one cell, one run, one process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse]

The harness knows no cell, configuration, traffic mix or metric by
name. ``BENCHMARK.json`` (the registry, at the repository's root) names
them; each lives in a file of its own that is found by that name:

- ``configs/<config>.json``   one deployment and the name of its builder
- ``builders/<builder>.py``   ``build(cfg, sizes, seed, mesh, log)``
- ``traffic/<traffic>.json``  one mix and the name of its loop
- ``loops/<loop>.py``         ``run(dep, traffic, seconds, seed, h)``
- ``layers/<metric>.py``      ``read(ctx)`` for one per-layer metric

It runs the program's defaults through its public entry points, sets no
``PYLOPS_MPI_TPU_*`` knob, refuses without a TPU (exit 2, no result
line) and prints as the last line of standard output the one JSON
object the driver reads. ``--rehearse`` is the only CPU mode: the
configuration's tiny sizes on virtual CPU devices, ``platform: cpu``,
counts and correctness only — never a device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # as near to process start as we get

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(HERE, "out")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``."""
    return importlib.import_module(f"chipbench.{kind}.{name}")


def registry() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def lookup(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, cfg, traffic


def metrics_for(bench: dict, section: str, workload: str) -> list:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


class Compiles:
    """Counts, through ``jax.monitoring``, the programs that reached
    the compiler (``requests``) and those of them the persistent cache
    served (``hits``). Copy of ``chip_smoke.Compiles``."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        mon.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.requests, self.hits)


class Handle:
    """What a loop gets from the harness."""

    def __init__(self, compiles, tol, traced, pre_s, logdir):
        self.log = log
        self.compiles = compiles
        self.tol = tol
        self.traced = traced
        self.pre_s = pre_s
        self.logdir = logdir
        self.phases = {}
        self.t_window = None
        self.tracing = False
        self._slice = None
        self._requests_at_start = 0

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name + "_s"] = self.phases.get(name + "_s", 0.0) \
                + time.perf_counter() - t0

    def span(self, name):
        """The benchmark's own span around one of its calls; lands in
        the profiler's trace when one is being taken."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("cb." + name)

    def reference(self, dep, k: int, niter: int, seed: int):
        """``k`` seeded right-hand sides, their plain reference answers
        and the reference's distance from the true models, which has
        to be inside the tolerance: ``(Y, Xref, err)``."""
        from chipbench import stats
        with self.phase("reference"):
            Y, Xt = dep.rhs(k, seed)
            Xref = dep.reference(Y, niter)
            err = max(stats.rel_err(Xref[:, j], Xt[:, j]) for j in range(k))
        if not err <= self.tol:
            raise RuntimeError(f"the plain reference is {err:.2e} from the "
                               f"true model (> {self.tol:.0e})")
        return Y, Xref, err

    @contextlib.contextmanager
    def must_not_compile(self, what: str):
        """The repeat of a warmed call: nothing may reach the compiler."""
        before = self.compiles.snapshot()
        yield
        if self.compiles.snapshot() != before:
            raise RuntimeError(f"{what} reached the compiler again: "
                               f"{before} -> {self.compiles.snapshot()}")

    def slice_at(self, elapsed: float) -> None:
        """Open, once ``pre_s`` of the window have passed, the
        ``cb.slice`` span the per-layer numbers are taken over."""
        if self._slice is None and elapsed >= self.pre_s:
            self._slice = self.span("slice")
            self._slice.__enter__()

    def end_slice(self) -> None:
        if self._slice not in (None, False):
            self._slice.__exit__(None, None, None)
        self._slice = False            # closed: never opened again

    def compiled_since_start(self) -> int:
        """Compile requests since the window's first instant."""
        return self.compiles.snapshot()[0] - self._requests_at_start

    def start_window(self) -> float:
        """Called by the loop at the first instant of its window: ends
        set-up, starts the profiler in a traced run, returns the
        window's zero on ``time.perf_counter``."""
        if self.traced:
            import jax
            shutil.rmtree(self.logdir, ignore_errors=True)
            os.makedirs(self.logdir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.tracing = True
        self._requests_at_start = self.compiles.snapshot()[0]
        self.t_window = time.perf_counter()
        return self.t_window

    def stop_trace(self):
        if self.tracing:
            import jax
            self.tracing = False
            jax.profiler.stop_trace()


def device_report(devs) -> dict:
    d0 = devs[0]
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def open_cell(workload: str, rehearse: bool):
    """The cell's registry entries and files; for a rehearsal the tiny
    sizes, and the environment that gives JAX the CPU with as many
    virtual devices as the cell has chips (before JAX is imported)."""
    bench = registry()
    cell, cfg, traffic = lookup(bench, workload)
    sizes = dict(cfg["sizes"])
    if rehearse:
        sizes.update(cfg["rehearse"])
        traffic = dict(traffic, **traffic.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(flags + [
            "--xla_force_host_platform_device_count=%d" % cell["chips"]])
    return bench, cell, cfg, traffic, sizes


def attach(workload: str, chips: int, rehearse: bool):
    """Initialise JAX and the program for ``chips`` devices: refuse
    (``SystemExit(2)``, the platform named, nothing on stdout) without
    a TPU or with too few chips, arm the compile cache by the
    repository's one rule, build the mesh. Returns ``(devices, mesh,
    compile counter, cache directory)``."""
    import jax
    platform = jax.default_backend()
    devs = jax.devices()
    if platform != ("cpu" if rehearse else "tpu") or len(devs) < chips:
        print(f"chipbench: JAX found platform {platform!r} with "
              f"{len(devs)} x {devs[0].device_kind}; {workload} needs "
              f"{chips} TPU chip(s) (--rehearse runs the tiny CPU twin)",
              file=sys.stderr)
        raise SystemExit(2)
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import aot
    cache_dir = aot.maybe_enable_compile_cache(
        os.path.join(ROOT, ".jax_cache"))
    compiles = Compiles()
    mesh = pmt.make_mesh(chips)
    pmt.set_default_mesh(mesh)
    return devs[:chips], mesh, compiles, cache_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny sizes on virtual CPU "
                         "devices; proves the script, never the chip")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic, sizes = open_cell(args.workload,
                                                 args.rehearse)
    chips = int(cell["chips"])
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    devs, mesh, compiles, cache_dir = attach(args.workload, chips,
                                             args.rehearse)
    from chipbench import costs, trace_reduce
    peak_row = None if args.rehearse else costs.peaks(devs[0].device_kind)
    log(f"{args.workload}: seed {args.seed}, {seconds:g}s, trace "
        f"{args.trace}, {len(devs)} x {devs[0].device_kind}, compile "
        f"cache {cache_dir}")

    traced = bool(args.trace) and not args.rehearse
    tr = traffic.get("trace", {})
    pre_s = float(tr.get("pre_s", 0.0)) if traced else 0.0
    if traced:
        seconds = min(seconds, pre_s + float(tr.get("slice_s", seconds)))
    logdir = os.path.join(OUT, "trace", args.workload)
    h = Handle(compiles, float(cfg["guarantees"]["rel_tol"]), traced, pre_s,
               logdir)

    h.phases["import_and_backend_s"] = time.perf_counter() - T_START
    dep = find("builders", cfg["builder"]).build(
        cfg, sizes, args.seed, mesh, log)
    log(f"built: {dep.describe}")

    try:
        rec = find("loops", traffic["loop"]).run(
            dep, traffic, seconds, args.seed, h)
    finally:
        h.stop_trace()

    setup_s = h.t_window - T_START
    requests, hits = compiles.snapshot()
    device = device_report(devs)
    split = {**dep.split, **h.phases}
    detail = {
        "workload": args.workload, "seed": args.seed, "seconds": seconds,
        "trace": args.trace, "rehearse": args.rehearse,
        "deployment": dep.describe, "setup_s": setup_s,
        "setup_split_s": split,
        "compile": {"dir": cache_dir, "requests": requests, "hits": hits,
                    "compiled": requests - hits,
                    "requests_in_window":
                        rec["compile_requests_in_window"]},
        "records": {k: v for k, v in rec.items()
                    if k not in ("latencies_s", "late_s", "fills")},
        "samples": len(rec["latencies_s"]),
    }

    ctx = {"records": rec, "config": cfg, "sizes": sizes, "traffic": traffic,
           "cell": cell, "chips": chips, "deployment": dep,
           "peaks": peak_row, "setup_s": setup_s, "device": device,
           "compile": detail["compile"], "trace": None, "log": log}
    if traced:
        path = trace_reduce.newest_xplane(logdir)
        ctx["trace"] = trace_reduce.load(path)
        detail["xplane"] = os.path.relpath(path, ROOT)

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, section, args.workload):
        if args.rehearse and m["source"] != "program_counter":
            continue               # a CPU run names no device metric
        value = find("layers" if args.trace else "metrics",
                     m["name"]).read(ctx)
        if value is None:
            continue               # nothing to read: left out
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    correct = (rec["failed"] == 0 and rec["attempted"] > 0
               and rec["compile_requests_in_window"] == 0)
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics,
              "device": device}
    if ctx["trace"] is not None:
        t = ctx["trace"]
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        worst = min(t.devices, key=lambda d: trace_reduce.total(t.busy(d))) \
            if t.devices else None
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in t.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in
                          (t.gaps(worst, 10) if worst else [])]}
    print(json.dumps(detail, default=str))
    # the file also keeps the result and every sample (in due order)
    detail.update(result=result, latencies_s=rec["latencies_s"],
                  late_s=rec.get("late_s"))
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{args.workload}.seed{args.seed}."
                                f"trace{args.trace}.json"), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
