"""Traffic loop ``open_serve``: an open loop through ``SolveDaemon``
over one ``WarmPool`` family, the service's default K buckets, window
and queue bound.

Requests are DUE at the instants of one Poisson process conditioned on
its count (``round(rate * seconds)`` requests), drawn from the mix's own
``arrival_seed`` and so the same in every run: the pattern of arrivals
alone moves the tail by more than any change to the service would
(PERF.md section 6), so it belongs to the mix, like the rate. ``--seed``
draws the operator, the right-hand sides and the column each request
carries. Requests are timed from the instant they were due to the
answer in the client's hands. Two threads: the generator (this one) submits on schedule, one
collector waits on the tickets in order. A request that is refused
(``QueueFull``), fails, times out or answers wrongly counts as missing
(infinite latency, ``failed``).
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from chipbench import stats


def run(dep, traffic: dict, seconds: float, seed: int, h) -> dict:
    from pylops_mpi_tpu.serving import (FamilySpec, QueueFull, SolveDaemon,
                                        WarmPool)

    niter, ncol = int(traffic["niter"]), int(traffic["pool"])
    rate = float(traffic["rate_per_s"])
    fam = "family"
    Y, Xref, ref_err = h.reference(dep, ncol, niter, seed)
    cols = np.ascontiguousarray(Y.T)              # one request a row
    XrefT = np.ascontiguousarray(Xref.T)
    del Y, Xref

    pool = WarmPool()
    pool.register(FamilySpec(name=fam, operator=dep.op,
                             solver=traffic["solver"], niter=niter,
                             tol=0.0))
    daemon = SolveDaemon(pool)
    try:
        with h.phase("warmup"):
            daemon.start(prewarm=True)
            # every bucket once with real columns, then everything
            # again under the compile count
            for b in pool.buckets:
                _burst(daemon, fam, cols, b)
            with h.must_not_compile("a warmed bucket"):
                pool.prewarm()
                _burst(daemon, fam, cols, 1)

        due = stats.arrivals(rate, seconds, int(traffic["arrival_seed"]))
        n = len(due)
        pick = np.random.default_rng([int(seed), 0xC01]).integers(
            0, ncol, size=n)
        sent = [None] * n
        answered = [None] * n
        answers = [None] * n
        handoff: "queue.Queue" = queue.Queue()
        limit = float(traffic.get("answer_timeout_s", 60.0))

        def collect():
            while True:
                item = handoff.get()
                if item is None:
                    return
                i, ticket = item
                try:
                    with h.span("wait"):
                        res = ticket.wait(timeout=limit)
                except Exception as exc:   # failed batch or timeout
                    answers[i] = exc
                    continue
                answered[i] = time.perf_counter()
                answers[i] = res

        collector = threading.Thread(target=collect, name="cb-collect",
                                     daemon=True)
        collector.start()
        t0 = h.start_window()
        for i in range(n):
            now = time.perf_counter() - t0
            h.slice_at(now)
            if due[i] > now:
                time.sleep(due[i] - now)
            with h.span("submit"):
                try:
                    ticket = daemon.submit(fam, cols[pick[i]])
                except QueueFull as exc:
                    answers[i] = exc
                    ticket = None
            sent[i] = time.perf_counter() - t0
            if ticket is not None:
                handoff.put((i, ticket))
        rest = seconds - (time.perf_counter() - t0)
        if rest > 0:
            time.sleep(rest)
        h.end_slice()
        handoff.put(None)
        collector.join(timeout=limit + 30.0)
        if collector.is_alive():
            raise RuntimeError("the collector did not finish")
        in_window = h.compiled_since_start()   # the drain included
        served = daemon.stats()
    finally:
        drained = daemon.drain(timeout=60.0)
    if not drained:
        raise RuntimeError("the daemon did not drain")

    ok, errs, fills = [], [], []
    in_time = 0
    for i in range(n):
        res = answers[i]
        good = isinstance(res, dict)
        if good:
            e = stats.rel_err(res["x"], XrefT[pick[i]])
            errs.append(e)
            good = e <= h.tol
            fills.append((int(res["batch_k"]), int(res["bucket"])))
            in_time += answered[i] - t0 <= seconds
        ok.append(bool(good))
    lat = stats.open_loop_latencies(
        due, [None if a is None else a - t0 for a in answered], ok)
    return {
        "attempted": n, "failed": n - sum(ok), "completed": sum(ok),
        "answered_in_window": int(in_time),
        "window_s": float(seconds), "latencies_s": lat,
        "late_s": stats.lateness(due, sent),
        "iterations_per_solve": niter, "fills": fills,
        "service": served,
        "err_max": max(errs) if errs else None, "ref_err_true": ref_err,
        "offered_per_s": rate,
        "compile_requests_in_window": in_window,
    }


def _burst(daemon, fam, cols, k):
    """``k`` requests at once (one batch of bucket ``k``), answered."""
    tickets = [daemon.submit(fam, cols[j % len(cols)]) for j in range(k)]
    for t in tickets:
        t.wait(timeout=600.0)
