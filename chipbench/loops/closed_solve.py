"""Traffic loop ``closed_solve``: one caller, ``pmt.cgls`` on a single
right-hand side, again as soon as the answer is ready.

The caller's clock runs from the call to ``block_until_ready`` of the
answer. Right-hand sides cycle through a seeded pool placed on the
device in set-up. After every solve one small jitted program computes
the answer's relative error against the plain reference ON the device
and leaves a scalar there; the scalars are read after the window, so
every answer is checked and none is pulled inside the window.
"""

from __future__ import annotations

import time


def run(dep, traffic: dict, seconds: float, seed: int, h) -> dict:
    """``h`` is the harness handle: ``h.log``, ``h.compiles``,
    ``h.span(name)``, ``h.phase(name)``, ``h.tol``, ``h.reference()``,
    ``h.must_not_compile()``, ``h.start_window()``, ``h.slice_at()``,
    ``h.end_slice()``, ``h.compiled_since_start()`` (``run.Handle``)."""
    import jax
    import jax.numpy as jnp
    import pylops_mpi_tpu as pmt

    niter, pool = int(traffic["niter"]), int(traffic["pool"])
    Y, Xref, ref_err = h.reference(dep, pool, niter, seed)
    with h.phase("warmup"):
        ys = [pmt.DistributedArray.to_dist(Y[:, j], mesh=dep.mesh)
              for j in range(pool)]
        refs = [pmt.DistributedArray.to_dist(Xref[:, j], mesh=dep.mesh).array
                for j in range(pool)]
        err_fn = jax.jit(lambda x, r: jnp.sqrt(
            jnp.sum((x - r) ** 2) / jnp.sum(r * r)))

        def solve(j):
            x = pmt.cgls(dep.op, ys[j], niter=niter, tol=0.0)[0]
            jax.block_until_ready(x.array)
            return x

        def call(j):
            return err_fn(solve(j).array, refs[j])

        float(call(0))
        with h.must_not_compile("the repeated warmed solve"):
            e = float(call(1 % pool))
        if not e <= h.tol:
            raise RuntimeError(f"warm-up answer is {e:.2e} from the "
                               "reference")

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
            x = solve(j)
        lat.append(time.perf_counter() - ta)
        with h.span("check"):
            errs.append(err_fn(x.array, refs[j]))
        i += 1
    t_end = time.perf_counter()
    h.end_slice()
    errs = [float(e) for e in errs]
    bad = [e for e in errs if not e <= h.tol]
    return {
        "attempted": len(errs), "failed": len(bad),
        "completed": len(errs) - len(bad),
        "window_s": t_end - t0, "latencies_s": lat,
        "iterations_per_solve": niter, "columns": 1,
        "err_max": max(errs) if errs else None, "ref_err_true": ref_err,
        "compile_requests_in_window": h.compiled_since_start(),
    }
