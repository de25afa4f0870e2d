"""Traffic loop ``closed_vstack``: one caller, ``pmt.cgls`` on a stack of
local operators whose model is REPLICATED (``Partition.BROADCAST``) and
whose data is SCATTERED over the stack's rows (``Partition.SCATTER``),
from a zero start, again as soon as the answer is ready — upstream
``tutorials/lsm.py``'s solve, the lines of ``pylops_mpi_tpu.models.lsm``
with the operator built once::

    y = dep.vector(Op.shape[0], d)           # SCATTER over the shots
    x0 = dep.vector(Op.shape[1])             # BROADCAST, zeros
    x = pmt.cgls(Op, y, x0=x0, niter=niter, tol=0)[0]

What differs from ``closed_broadcast``, and why it is a loop of its own:
the iterate the caller waits for is not one float32 determines. On the
Kirchhoff operator ANY two float32 solves of one problem drift apart
tenfold an iteration from the sixth or so on — the plain reference
against its own sums in another order among them (1.3e-2 after ten, on
the chip: ``chipbench/scratch/lsm_account.py``, PERF.md section 6,
PR 38) — so "within ``rel_tol`` of the reference after the same
iterations" can be kept by no program at the depth the traffic runs,
and the bfloat16 control reads no worse there than the program. The
guarantee is therefore made of what float32 DOES determine, three
limits (:func:`judge` is the whole comparison):

- ``rel_tol``: the answer of THE SAME CALL after ``hold_niter``
  iterations (the depth up to which float32 determines it) within
  ``rel_tol`` of the plain reference's after as many, for every pool
  member, in the warm-up. The start is zero, so the answer IS the
  correction and a product at a lower precision shows at first order:
  this is the limit that refuses the control;
- ``resid_ratio``: the residual of every pool member's FULL-depth
  answer — by the builder's plain forward in a program of its own
  (``dep.drop``) — over the plain reference's own after as many
  iterations: the answer the caller gets fits the data as well as the
  reference's does, whichever way the two drifted. A solve that stops
  early, or does not descend, is refused here;
- ``repeat_tol``: every answer of the window against the warm-up's
  answer for the same data. One program on one device is repeatable;
  an answer of the window that is not the warm-up's is a fault.

``dep.stand_in``, when set, is called in the program's place
(``f(y, x0, niter) -> x``): how a deliberately wrong solve
(``dep.control``) is shown to come out as not correct through this very
comparison (``chipbench/tests/test_lsm_cell.py``; once on the chip,
PERF.md section 6, PR 38). No cell sets it.

As in ``closed_broadcast``: the references are computed one at a time
BEFORE the pool's vectors are placed; the caller's clock runs from the
call to ``block_until_ready`` of the answer; after every solve one
small jitted program leaves the answer's distance from the warm-up's on
the device and the loop waits for it without reading it; the scalars
are read after the window.
"""

from __future__ import annotations

import time


def judge(readings: dict, limits: dict) -> list:
    """The comparison that decides ``correct``: the names of the
    readings that are not within their limit (a NaN is not)."""
    return [k for k, v in readings.items() if not v <= limits[k]]


def run(dep, traffic: dict, seconds: float, seed: int, h) -> dict:
    """``h`` is the harness handle (``run.Handle``), as in
    ``closed_solve.run``."""
    import jax
    import jax.numpy as jnp
    import pylops_mpi_tpu as pmt

    niter, pool = int(traffic["niter"]), int(traffic["pool"])
    hold = int(traffic["hold_niter"])
    limits = {"rel_tol": h.tol, "resid_ratio": dep.resid_ratio,
              "repeat_tol": dep.repeat_tol}
    err_fn = jax.jit(lambda x, r: jnp.sqrt(
        jnp.sum((x - r) ** 2) / jnp.sum(r * r)))

    def program(y, x0, niter_):
        return pmt.cgls(dep.op, y, x0=x0, niter=niter_, tol=0.0)[0]

    def solve(y, x0, niter_=niter):
        x = (dep.stand_in or program)(y, x0, niter_)
        jax.block_until_ready(x.array)
        return x

    def held(what: str, readings: dict):
        bad = judge(readings, limits)
        if bad:
            raise RuntimeError(
                f"{what}: " + ", ".join(f"{k} {readings[k]:.3e} > "
                                        f"{limits[k]:.1e}" for k in bad))

    with h.phase("reference"):
        shallow, drops = [], []
        for j in range(pool):
            d = dep.rhs(j, seed)
            shallow.append(jax.block_until_ready(dep.reference(d, hold).x))
            drops.append(float(dep.reference(d, niter).drop))
    with h.phase("warmup"):
        ys = [dep.vector(dep.nrows, dep.rhs(j, seed)) for j in range(pool)]
        x0 = dep.vector(dep.ncols)          # upstream's x0: zeros

        def check(j):
            """Pool member ``j``'s two readings, each held as soon as it
            is read, and its full-depth answer."""
            e = float(err_fn(solve(ys[j], x0, hold).array, shallow[j]))
            held("warm-up", {"rel_tol": e})
            x = solve(ys[j], x0)
            ratio = float(dep.drop(ys[j].array, x.array)) / drops[j]
            held("warm-up", {"resid_ratio": ratio})
            return e, ratio, x.array

        first = check(0)                    # compiles both depths
        # the window's own check, once before it (compiles it too)
        held("warm-up", {"repeat_tol": float(err_fn(
            solve(ys[0], x0).array, first[2]))})
        with h.must_not_compile("the pool's other solves: one executable "
                                "a depth"):
            rows = [first] + [check(j) for j in range(1, pool)]
        own = [r[2] for r in rows]

    lat, errs = [], []
    t0 = h.start_window()
    i = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        h.slice_at(now - t0)
        j = i % pool
        ta = time.perf_counter()
        with h.span("solve"):
            x = solve(ys[j], x0)
        lat.append(time.perf_counter() - ta)
        with h.span("check"):
            # waited for, not read: the next solve's clock must not
            # hold the check's device time
            errs.append(jax.block_until_ready(err_fn(x.array, own[j])))
        del x
        i += 1
    t_end = time.perf_counter()
    h.end_slice()
    errs = [float(e) for e in errs]
    bad = [e for e in errs if judge({"repeat_tol": e}, limits)]
    return {
        "attempted": len(errs), "failed": len(bad),
        "completed": len(errs) - len(bad),
        "window_s": t_end - t0, "latencies_s": lat,
        "iterations_per_solve": niter, "columns": 1,
        "err_max": max(errs) if errs else None,
        "warmup_err": max(r[0] for r in rows),
        "warmup_resid_ratio": max(r[1] for r in rows),
        "ref_resid_drop": max(drops),
        "compile_requests_in_window": h.compiled_since_start(),
    }
