#!/usr/bin/env python3
"""The account of ``lsm_kirchhoff``'s limits, on the chip at the
configuration's sizes: how far apart float32 solves of ONE problem lie
after every iteration up to ``--niter``, each read against the plain
reference's iterate of the same depth (PERF.md section 6, PR 38). A
scratch script run by hand through the chip tool, never imported:

    python3 chipbench/scratch/lsm_account.py [--seeds 1,2,3 --niter 10]
    python3 chipbench/scratch/lsm_account.py --rehearse

The witnesses, a seed (``x`` after every iteration where the recurrence
is the script's own, which is ``chipbench/reference.py``'s with the
iterates kept):

- ``reference``: the builder's plain operator under textbook CGLS — what
  every other row is read against;
- ``reordered``: the SAME plain operator with its pixels in runs of
  another shape (``--reorder 16x64``): the same float32 products summed
  in another order, nothing else;
- ``control``: the plain operator with bfloat16 products (the builder's
  ``CONTROLS``);
- ``program_op``: the PROGRAM's operator (``MPILSM``'s ``matvec`` /
  ``rmatvec``: the Pallas kernels, ``pmt_conv1d``) under the textbook
  recurrence;
- ``program``: ``pmt.cgls`` on ``MPILSM`` — the timed call (final
  iterate only);
- ``plain_op_fused``: the plain operator under ``pmt.cgls`` (final
  iterate only).

``program_op`` against ``program`` tells the recurrence from the
operator; ``reordered`` says what the arithmetic alone does. Prints one
JSON line a seed and writes them all to
``chiprun_out/lsm_account<--tag>.json``. ``--sizes '{"z0": 0.0}'`` is
the survey with its image's first row IN the acquisition surface, where
float32 CGLS is not reproducible (PERF.md section 6).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def recorded_cgls(mv, rmv, d, niter: int):
    """``chipbench/reference.py::cgls``'s arithmetic with every iterate
    kept: ``(niter, n)``, row ``k`` the answer after ``k + 1``
    iterations."""
    import jax
    import jax.numpy as jnp

    def dot(u):
        return jnp.sum(u * u)
    s = d
    r = rmv(s)
    c = r
    q = mv(c)
    x = jnp.zeros_like(r)

    def body(st, _):
        x, s, c, q, kold = st
        a = kold / dot(q)
        x = x + a * c
        s = s - a * q
        r = rmv(s)
        k = dot(r)
        c = r + (k / kold) * c
        return (x, s, c, mv(c), k), x
    return jax.lax.scan(body, (x, s, c, q, dot(r)), None, length=niter)[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--niter", type=int, default=10)
    ap.add_argument("--reorder", default="16x64",
                    help="the reordered witness's run of pixels (z x x)")
    ap.add_argument("--sizes", default="{}",
                    help="JSON: sizes to lay over the configuration's")
    ap.add_argument("--witnesses", default="reordered,control,program_op,"
                    "program,plain_op_fused")
    ap.add_argument("--tag", default="", help="the report's name suffix")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    from chipbench import run
    _, cell, cfg, _, sizes = run.open_cell("lsm_kirchhoff.cgls_shots8",
                                           a.rehearse)
    sizes.update(json.loads(a.sizes))
    devs, mesh, _, _ = run.attach(cell["name"], 1, a.rehearse)

    import numpy as np
    import jax
    import jax.numpy as jnp
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.linearoperator import register_operator_arrays
    from pylops_mpi_tpu.ops.local import LocalOperator
    from chipbench.builders import lsm as B

    niter = a.niter
    dep = B.build(dict(cfg, guarantees=dict(cfg["guarantees"], niter=niter)),
                  sizes, 0, mesh, run.log)
    Op, times, width = dep.op, dep.times, dep.width

    def plain(cast=None):
        mv, rmv = B.plain_system(sizes, cast, width)

        @jax.jit
        def solve(times, d):
            with jax.default_matmul_precision("highest"):
                return recorded_cgls(lambda c: mv(times, c),
                                     lambda s: rmv(times, s), d, niter)
        return solve

    # the same plain operator, its pixels in runs of another shape
    sizes2 = dict(sizes, run=tuple(int(n) for n in a.reorder.split("x")))
    times2 = jax.block_until_ready(B.point_times(sizes2))
    width2 = B.band_width(sizes2, times2)
    mv2, rmv2 = B.plain_system(sizes2, None, width2)

    @jax.jit
    def reordered(times, d):
        with jax.default_matmul_precision("highest"):
            return recorded_cgls(lambda c: mv2(times, c),
                                 lambda s: rmv2(times, s), d, niter)

    @jax.jit
    def program_op(op, d):
        return recorded_cgls(
            lambda c: op.matvec(dep.vector(dep.ncols, c)).array,
            lambda s: op.rmatvec(dep.vector(dep.nrows, s)).array, d, niter)

    class PlainKirchhoff(LocalOperator):
        """The builder's plain operator as a local operator of the
        program's, its travel times a pytree child."""

        def __init__(self, times):
            self.times = times
            self.mv, self.rmv = B.plain_system(sizes, None, width)
            super().__init__(dep.ncols, dep.nrows, dtype=np.float32)

        def _matvec(self, x):
            with jax.default_matmul_precision("highest"):
                return self.mv(self.times, x)

        def _rmatvec(self, x):
            with jax.default_matmul_precision("highest"):
                return self.rmv(self.times, x)
    register_operator_arrays(PlainKirchhoff, "times")
    PlainOp = pmt.MPIVStack([PlainKirchhoff(times)], mesh=mesh)

    @jax.jit
    def errs(xs, ref):
        return jnp.sqrt(jnp.sum((xs - ref) ** 2, axis=-1)
                        / jnp.sum(ref * ref, axis=-1))
    x0 = dep.vector(dep.ncols)
    reference, control = plain(), plain(B.CONTROLS["bf16"]["cast"])
    out = []
    for seed in (int(s) for s in a.seeds.split(",")):
        d = dep.rhs(0, seed)
        y = dep.vector(dep.nrows, d)
        row = {"niter": niter, "seed": seed}
        ref = None
        for name, solve in (
                ("reference", lambda: reference(times, d)),
                ("reordered", lambda: reordered(times2, d)),
                ("control", lambda: control(times, d)),
                ("program_op", lambda: program_op(Op, d)),
                ("program", lambda: pmt.cgls(
                    Op, y, x0=x0, niter=niter, tol=0.0)[0].array[None]),
                ("plain_op_fused", lambda: pmt.cgls(
                    PlainOp, y, x0=x0, niter=niter, tol=0.0)[0].array[None])):
            if ref is not None and name not in a.witnesses.split(","):
                continue
            t0 = time.perf_counter()
            xs = jax.block_until_ready(solve())
            row[name + "_s"] = round(time.perf_counter() - t0, 3)
            if ref is None:
                ref = xs
                row["resid_drop"] = float(dep.drop(d, xs[-1]))
            else:
                # every kept iterate against the reference's of its depth
                row[name] = [float("%.4g" % e)
                             for e in errs(xs, ref[-xs.shape[0]:])]
                row[name + "_drop"] = float(dep.drop(d, xs[-1]))
        out.append(row)
        print(json.dumps(row), flush=True)
    report = {"device": devs[0].device_kind, "sizes": sizes, "width": width,
              "reordered": {"block": a.reorder, "width": width2},
              "peak_bytes": (devs[0].memory_stats() or {}).get(
                  "peak_bytes_in_use"), "rows": out}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"lsm_account{a.tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
