"""Traffic loop ``closed_stacked``: one caller, ``pmt.cgls`` on a
STACKED system — data ``StackedDistributedArray([d, 0])``, a starting
model ``x0`` — again as soon as the answer is ready.

What differs from ``closed_solve``: the data is a stacked pair with a
zero second half, every solve starts from its pool member's own ``x0``,
the pool and its plain references are made on the device by the
builder (``dep.rhs``, ``dep.reference``; nothing volume-sized crosses
the host), and the guarantee is agreement with the reference — not
distance from a true model, which an ill-posed system does not promise.
Three limits (:func:`judge` is the whole comparison):

- ``rel_tol``: every answer of the window within it of its reference;
- ``corr_tol``: THE SAME SOLVE IN ITS CORRECTION FORM — the timed
  solver program given the residual ``[d, 0] - A x0`` and a zero start
  — within it of the reference's correction, every pool member once in
  set-up. That form is the limit that feels the operator: beside a
  model at level 8 float32 hides what a lower-precision convolution
  changes, beside the correction alone it does not (the builder's
  docstring). The pool's own solve afterwards must not compile: it is
  the executable the correction form just ran;
- ``resid_drop``: the reference's own residual after the iterations
  over its first.

``dep.stand_in``, when set, is called in the program's place
(``f(y, x0) -> x``): how a deliberately wrong solve (``dep.control``) is
shown to come out as not correct through this very comparison
(``chipbench/tests/test_poststack_cell.py``; once on the chip, PERF.md
section 6, PR 32). No cell sets it.

The references are computed one at a time BEFORE the pool is placed,
each from a pool member that is dropped again, so the reference's
carries and the solver's never share the chip.

As there: the caller's clock runs from the call to
``block_until_ready`` of the answer; after every solve one small jitted
program leaves the answer's relative error against its reference on the
device, and the scalars are read after the window. Unlike there the
loop waits for that program (without reading its scalar) before the
next call: over two 805 MB volumes it runs for milliseconds, and left in
flight it would sit at the head of the next solve's device time and
start before the solver's own span (the trace's clock check refuses
that).
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
    limits = {"rel_tol": h.tol, "corr_tol": dep.corr_tol,
              "resid_drop": dep.resid_drop}
    err_fn = jax.jit(lambda x, r: jnp.sqrt(
        jnp.sum((x - r) ** 2) / jnp.sum(r * r)))

    def program(y, x0):
        return pmt.cgls(dep.op, y, x0=x0, niter=niter, tol=0.0)[0]

    def solve(y, x0):
        x = (dep.stand_in or program)(y, x0)
        jax.block_until_ready(x.array)
        return x

    def held(what: str, readings: dict):
        bad = judge(readings, limits)
        if bad:
            raise RuntimeError(
                f"{what}: " + ", ".join(f"{k} {readings[k]:.3e} > "
                                        f"{limits[k]:.1e}" for k in bad))

    with h.phase("reference"):
        refs, drops, corrs = [], [], []
        zero = dep.vector()
        for j in range(pool):
            d, x0 = dep.rhs(j, seed)
            ref = dep.reference(d, x0, niter)
            del d, x0
            refs.append(jax.block_until_ready(ref.x))
            drops.append(float(ref.drop))
            # the same solve in its correction form, by the timed program
            y = pmt.StackedDistributedArray([dep.vector(ref.r0),
                                             dep.vector(ref.r1)])
            del ref.r0, ref.r1
            corrs.append(float(err_fn(solve(y, zero).array, ref.dx)))
            del y, ref
        held("set-up", {"resid_drop": max(drops), "corr_tol": max(corrs)})
    with h.phase("warmup"):
        ys, x0s = [], []
        for j in range(pool):
            d, x0 = dep.rhs(j, seed)
            ys.append(pmt.StackedDistributedArray([dep.vector(d), zero]))
            x0s.append(dep.vector(x0))
            del d, x0

        def call(j):
            return err_fn(solve(ys[j], x0s[j]).array, refs[j])

        with h.must_not_compile("the pool's solves after their "
                                "correction form: one executable"):
            e = max(float(call(j)) for j in range(pool))
        held("warm-up", {"rel_tol": e})

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
            x = solve(ys[j], x0s[j])
        lat.append(time.perf_counter() - ta)
        with h.span("check"):
            # waited for, not read: a check of two volumes is 2 ms of
            # device time, which the next solve's clock must not hold
            errs.append(jax.block_until_ready(err_fn(x.array, refs[j])))
        del x
        i += 1
    t_end = time.perf_counter()
    h.end_slice()
    errs = [float(e) for e in errs]
    bad = [e for e in errs if judge({"rel_tol": e}, limits)]
    return {
        "attempted": len(errs), "failed": len(bad),
        "completed": len(errs) - len(bad),
        "window_s": t_end - t0, "latencies_s": lat,
        "iterations_per_solve": niter, "columns": 1,
        "err_max": max(errs) if errs else None,
        "ref_resid_drop": max(drops), "corr_err_max": max(corrs),
        "compile_requests_in_window": h.compiled_since_start(),
    }
