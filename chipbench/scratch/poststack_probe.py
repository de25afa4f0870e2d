#!/usr/bin/env python3
"""Hand-run probe for the ``poststack_3d`` deployment, on the chip:

    python3 chipbench/scratch/poststack_probe.py [--ny 192] [--skip-account]

1. **The convolution's forms** at the configuration's size
   (``ny*nx`` traces of ``nt0`` samples, the configured wavelet):
   ``Conv1D`` (the program's path: the Pallas kernel ``pmt_conv1d``),
   the kernel called directly, ``Conv1D`` on an axis of 1,000 samples
   (padded to whole tiles), the builder's dense Toeplitz product and,
   when asked (``--forms lax_conv,shifted_slices``),
   ``lax.conv_general_dilated`` and shifted slices with a multiply-add
   each — milliseconds an apply (best of 3 x 5), error against the
   shifted slices on a background model and on its time derivative, and
   the compiled program's temporaries in volumes.
2. **The operator's applies**: ``StackOp.matvec`` / ``rmatvec`` jitted
   alone, ms.
3. **The account of the tolerances** (unless ``--skip-account``), a
   seed of ``--seeds`` each: the plain reference; the two wrong plain
   solves (``CONTROLS``: bfloat16 products, the central 31 taps) and
   the program (``pmt.cgls``, defaults), each as the distance of its
   ANSWER from the reference's (what ``rel_tol`` sees) and of its
   CORRECTION-FORM answer from the reference's correction (what
   ``corr_tol`` sees: the limit has to lie over the program's and
   under the two wrong ones').

Prints one JSON line a finding and writes them to
``chiprun_out/pr32/probe.json``. Refuses without a TPU.
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


def best(fn, *args, reps=3, inner=5):
    import jax
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / inner)
    return 1e3 * min(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ny", type=int, default=None)
    ap.add_argument("--skip-account", action="store_true")
    ap.add_argument("--skip-forms", action="store_true")
    ap.add_argument("--forms", default="Conv1D,pmt_conv1d,Conv1D_1000,"
                                       "dense_toeplitz")
    ap.add_argument("--seeds", default="3000000001,3000000002")
    ap.add_argument("--anywhere", action="store_true",
                    help="rehearse the script off the chip; its numbers "
                         "then mean nothing")
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not args.anywhere:
        print("poststack_probe: needs a TPU", file=sys.stderr)
        return 2
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    from pylops_mpi_tpu.ops.local import Conv1D
    from chipbench.builders import poststack as B

    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "poststack_3d.json")))
    sizes = dict(cfg["sizes"])
    if args.ny:
        sizes["ny"] = args.ny
    ny, nx, nt0 = sizes["ny"], sizes["nx"], sizes["nt0"]
    wav = B.ricker(sizes["ntwav_half"], sizes["dt"], sizes["f0"])
    nh, off = len(wav), len(wav) // 2
    vol = 4 * ny * nx * nt0
    found = []

    def say(**kw):
        found.append(kw)
        print(json.dumps(kw), flush=True)

    mesh = pmt.make_mesh(1)
    pmt.set_default_mesh(mesh)
    case = B.make_case(sizes, wav)
    d, x0 = case(jax.random.key(3000000001))
    rel = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)
                                        / jnp.sum(b * b)))

    if not args.skip_forms:
        v = x0.reshape(-1, nt0)
        h = jnp.asarray(wav)
        L = pk.conv1d_tile(nh)
        T3 = Conv1D._blocks(h, off, L)
        hi = jax.lax.Precision.HIGHEST

        def laxconv(v):
            return jax.lax.conv_general_dilated(
                v[:, None, :], h[None, None, :], (1,),
                [(off, nh - 1 - off)], precision=hi)[:, 0, :]

        def slices(v):
            n = v.shape[1]
            vp = jnp.pad(v, ((0, 0), (nh - 1 - off, off)))
            w = jnp.asarray(wav[::-1].copy())
            return jax.lax.fori_loop(
                0, nh, lambda k, y: y + w[k] * jax.lax.dynamic_slice_in_dim(
                    vp, k, n, axis=1), jnp.zeros_like(v))

        def conv_of(n):
            c = Conv1D((v.shape[0], n), h, axis=1, offset=off,
                       dtype=np.float32)
            return lambda u: c._matvec(u[:, :n].ravel()).reshape(-1, n)

        forms = {
            # the program's own path: the blocks made from h at run time
            "Conv1D": conv_of(nt0),
            "pmt_conv1d": lambda v: pk.conv1d_toeplitz(v, T3),
            # an axis that is not whole tiles: padded into the kernel
            "Conv1D_1000": conv_of(1000),
            "lax_conv": laxconv,
            "dense_toeplitz": lambda v: B.conv_t(v, wav, off),
            "shifted_slices": slices,
        }
        dv = jax.jit(B.deriv_t)(v)
        want, dwant = jax.jit(slices)(v), jax.jit(slices)(dv)
        for name, f in forms.items():
            if args.forms and name not in args.forms.split(","):
                continue
            try:
                c = jax.jit(f).lower(v).compile()
                temp = c.memory_analysis().temp_size_in_bytes / vol
                ms = best(c, v)
                n = c(v).shape[1]
                say(probe="conv_form", form=name, ms=ms,
                    err=float(rel(c(v), jax.jit(slices)(v[:, :n]))
                              if n != nt0 else rel(c(v), want)),
                    err_on_derivative=(None if n != nt0 else
                                       float(rel(c(dv), dwant))),
                    temp_volumes=temp, gb_per_s=2 * vol / ms / 1e6)
            except Exception as e:                      # noqa: BLE001
                say(probe="conv_form", form=name,
                    error=f"{type(e).__name__}: {str(e)[:300]}")
        del want, dwant, dv, v

        StackOp, Op, Lap = pmt.models.poststack_regularized(
            wav, nt0, (ny, nx), sizes["epsR"], mesh=mesh, dtype=np.float32)
        xv = pmt.DistributedArray(global_shape=ny * nx * nt0, mesh=mesh,
                                  dtype=np.float32)
        xv[:] = x0.ravel()
        fwd = jax.jit(lambda o, a: o.matvec(a))
        adj = jax.jit(lambda o, a: o.rmatvec(a))
        yv = fwd(StackOp, xv)
        say(probe="apply", which="StackOp.matvec", ms=best(fwd, StackOp, xv))
        say(probe="apply", which="StackOp.rmatvec", ms=best(adj, StackOp, yv))
        say(probe="apply", which="Op.matvec", ms=best(fwd, Op, xv))
        say(probe="apply", which="Lap.matvec", ms=best(fwd, Lap, xv))
        del yv, xv, StackOp, Op, Lap

    del d, x0
    if not args.skip_account:
        scale = float(np.float32(np.sqrt(sizes["epsR"])))
        niter = cfg["guarantees"]["niter"]
        ref = B.plain_solve(wav, scale, niter)
        wrongs = {}
        for kind, kw in B.CONTROLS.items():
            w = B.plain_solve(wav, scale, niter, **kw)
            # scalars out: the wrong solve's volumes never outlive it
            wrongs[kind] = (
                jax.jit(lambda d, x0, x, w=w: rel(w(d, None, x0)[0], x)),
                jax.jit(lambda r0, r1, dx, w=w: rel(
                    w(r0, r1, jnp.zeros_like(dx))[1], dx)))
        StackOp, Op, Lap = pmt.models.poststack_regularized(
            wav, nt0, (ny, nx), sizes["epsR"], mesh=mesh, dtype=np.float32)

        def vec(a=None):
            out = pmt.DistributedArray(global_shape=ny * nx * nt0, mesh=mesh,
                                       dtype=np.float32)
            if a is not None:
                out[:] = a.ravel()
            return out

        def program(y, x0):
            x, *_, cost = pmt.cgls(StackOp, y, x0=x0, niter=niter, tol=0.0)
            jax.block_until_ready(x.array)
            return x, cost

        zero = vec()
        for seed in (int(t) for t in args.seeds.split(",")):
            d, x0 = case(jax.random.key(seed))
            t0 = time.perf_counter()
            x, dx, r0, r1, drop = ref(d, None, x0)
            jax.block_until_ready(x)
            say(probe="account", seed=seed, what="float32 reference",
                drop=float(drop), seconds=time.perf_counter() - t0,
                dx_over_x=float(jnp.linalg.norm(dx.ravel())
                                / jnp.linalg.norm(x.ravel())))
            for kind, (ans, corr) in wrongs.items():
                say(probe="account", seed=seed, what=kind,
                    answer_err=float(ans(d, x0, x)),
                    correction_err=float(corr(r0, r1, dx)))
            y, xs = pmt.StackedDistributedArray([vec(d), zero]), vec(x0)
            del d, x0
            program(y, xs)
            t0 = time.perf_counter()
            got, cost = program(y, xs)
            ms = 1e3 * (time.perf_counter() - t0)
            still = [i for i in range(1, len(cost))
                     if cost[i] == cost[i - 1]]
            ans = float(rel(got.array, x.ravel()))
            del got, y, xs
            y = pmt.StackedDistributedArray([vec(r0), vec(r1)])
            del r0, r1
            got, ccost = program(y, zero)
            say(probe="account", seed=seed,
                what="the program (pmt.cgls, defaults)", answer_err=ans,
                correction_err=float(rel(got.array, dx.ravel())),
                solve_ms=ms, cost_first=float(cost[0]),
                cost_last=float(cost[-1]),
                cost_drop=float(cost[-1] / cost[0]),
                corr_cost_drop=float(ccost[-1] / ccost[0]),
                frozen_from=(still[0] if still else None))
            del got, y, x, dx
    peak = max(int((dv.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for dv in jax.devices())
    say(probe="memory", peak_bytes=peak, volumes=peak / vol)
    out = os.path.join(ROOT, "chiprun_out", "pr32")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "probe.json"), "w") as f:
        json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
