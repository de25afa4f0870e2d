"""Traffic loop ``closed_broadcast``: one caller, ``pmt.cgls`` on an
operator whose model and data are REPLICATED vectors
(``Partition.BROADCAST``), from a zero start, again as soon as the
answer is ready — upstream ``tutorials/mdd.py``'s solve, the lines of
``pylops_mpi_tpu.models.mdd`` with the operator built once::

    dy = DistributedArray(Op.shape[0], partition=BROADCAST); dy[:] = d
    x0 = DistributedArray(Op.shape[1], partition=BROADCAST)     # zeros
    x = pmt.cgls(Op, dy, x0=x0, niter=niter, tol=0)[0]

What differs from ``closed_solve``: the vectors are ``BROADCAST`` (it
makes ``SCATTER`` ones), the pool and its plain references are made on
the device by the builder (``dep.rhs``, ``dep.reference``; nothing
vector-sized crosses the host), and the guarantee is agreement with the
reference — ``closed_solve``'s ``h.reference`` holds the reference to
the TRUE model, which a deconvolution that is ill-posed outside its
band does not promise. Two limits (:func:`judge` is the whole
comparison):

- ``rel_tol``: every answer of the window — and of the warm-up — within
  it of its plain reference (2-norm over the model). The start is zero,
  so the answer IS the correction: float32 resolves it, and a product
  at a lower precision shows at first order;
- ``resid_drop``: the residual after the iterations over its first
  (the data's norm: the start is zero) — the reference's own, and, in
  the warm-up, that of every pool member's answer by the program
  (``dep.drop``: the builder's plain forward in a program of its own).

``dep.stand_in``, when set, is called in the program's place
(``f(y, x0) -> x``): how a deliberately wrong solve (``dep.control``) is
shown to come out as not correct through this very comparison
(``chipbench/tests/test_mdd_cell.py``; once on the chip, PERF.md
section 6, PR 34). No cell sets it.

The references are computed one at a time BEFORE the pool's vectors are
placed. As in ``closed_stacked``: the caller's clock runs from the call
to ``block_until_ready`` of the answer; after every solve one small
jitted program leaves the answer's relative error on the device and the
loop waits for it without reading it (left in flight it would sit at
the head of the next solve's device time); the scalars are read after
the window.
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
    limits = {"rel_tol": h.tol, "resid_drop": dep.resid_drop}
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
        refs, drops = [], []
        for j in range(pool):
            ref = dep.reference(dep.rhs(j, seed), niter)
            refs.append(jax.block_until_ready(ref.x))
            drops.append(float(ref.drop))
        held("set-up", {"resid_drop": max(drops)})
    with h.phase("warmup"):
        ys = [dep.vector(dep.nrows, dep.rhs(j, seed)) for j in range(pool)]
        x0 = dep.vector(dep.ncols)          # upstream's x0: zeros

        own = []

        def call(j):
            x = solve(ys[j], x0)
            own.append(float(dep.drop(ys[j].array, x.array)))
            return err_fn(x.array, refs[j])

        e = float(call(0))                  # compiles
        with h.must_not_compile("the pool's other solves: one executable"):
            e = max([e] + [float(call(j)) for j in range(pool)])
        held("warm-up", {"rel_tol": e, "resid_drop": max(own)})

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
        "err_max": max(errs) if errs else None, "warmup_err": e,
        "ref_resid_drop": max(drops), "own_resid_drop": max(own),
        "compile_requests_in_window": h.compiled_since_start(),
    }
