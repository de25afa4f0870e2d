#!/usr/bin/env python3
"""Hand-run probe for the Laplacian's forms at the ``poststack_3d``
cell's size, on the chip:

    python3 benchmarks/laplacian_probe.py [--ny 192 --nx 1024 --nt0 1024]
        [--strips 8,16,32,64] [--forms ring,three,slices,operator]

Milliseconds an apply (best of 3 x 5, ``block_until_ready``), forward
and adjoint, and the largest error against ``ops/local.py::Laplacian``
(the slice form) over the volume's norm, of

* ``ring`` — the program's kernel ``pmt_laplacian``
  (``pallas_kernels.laplacian_stencil``: one read, one write, a plane
  kept two grid steps in VMEM), called alone on the cube, once a strip
  height of ``--strips`` (the module's ``_LAP_STRIPS`` is set here, in
  the script: the program has no such knob);
* ``three`` — the simplest legal block form, kept HERE for the
  comparison only: the same plane arithmetic
  (``pallas_kernels._laplacian_plane``) with the cube passed three times
  under index maps ``i-1``, ``i``, ``i+1`` clamped (three reads, one
  write) and the centre copied into a one-plane scratch with zero rows
  either side;
* ``slices`` — ``ops/local.py::Laplacian`` jitted on the cube;
* ``operator`` — ``MPILaplacian.matvec`` / ``.rmatvec`` jitted on the
  flat ``DistributedArray`` (the reshape from the solver's flat carry
  and back included), with the form its rule chose.

Prints one JSON line a finding and writes them to
``chiprun_out/pr33/laplacian_probe.json``. Refuses without a TPU unless
``--anywhere`` (a rehearsal of the script at a tiny size; its times
mean nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def three_operand(x, n0, coef, adjoint, R):
    """The three-operand block form (module docstring)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    rows, n1, n2 = x.shape
    H = pk._LAP_HALO

    def kernel(xm_ref, xc_ref, xp_ref, o_ref, pad):
        i = pl.program_id(0)
        zeros = jnp.zeros((H, n2), x.dtype)
        pad[0:H, :] = zeros
        pad[H + n1:H + n1 + H, :] = zeros

        def fill(s, carry):
            r = pl.multiple_of(s * R, R)
            pad[pl.ds(r + H, R), :] = xc_ref[0, pl.ds(r, R), :]
            return carry
        jax.lax.fori_loop(0, n1 // R, fill, None)
        # the clamped neighbours are the plane itself: zero them
        first = (i > 0).astype(x.dtype)
        last = (i < rows - 1).astype(x.dtype)
        pk._laplacian_plane(
            o_ref, lambda r: pad[pl.ds(r, R + 2 * H), :],
            lambda r: first * xm_ref[0, pl.ds(r, R), :],
            lambda r: last * xp_ref[0, pl.ds(r, R), :], i,
            n0=n0, n1=n1, coef=coef, adjoint=adjoint, R=R)

    plane = (1, n1, n2)
    return pl.pallas_call(
        kernel, grid=(rows,),
        in_specs=[
            pl.BlockSpec(plane, lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            pl.BlockSpec(plane, lambda i: (i, 0, 0)),
            pl.BlockSpec(plane, lambda i: (jnp.minimum(i + 1, rows - 1),
                                           0, 0))],
        out_specs=pl.BlockSpec(plane, lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n1 + 2 * H, n2), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=pk._VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",)),
        interpret=pk._interpret(), name="probe_laplacian_three",
    )(x, x, x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ny", type=int, default=192)
    ap.add_argument("--nx", type=int, default=1024)
    ap.add_argument("--nt0", type=int, default=1024)
    ap.add_argument("--strips", default="8,16,32,64")
    ap.add_argument("--forms", default="ring,three,slices,operator")
    ap.add_argument("--seed", type=int, default=3000000331)
    ap.add_argument("--anywhere", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.anywhere:
        print(f"laplacian_probe: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    from pylops_mpi_tpu.ops.local import Laplacian
    from pylops_mpi_tpu.parallel.mesh import make_mesh

    dims = (args.ny, args.nx, args.nt0)
    coef = (1.0, 1.0, 1.0)          # the cell's: weights and samplings of 1
    forms = args.forms.split(",")
    strips = [int(s) for s in args.strips.split(",")]
    found = []

    def say(**kw):
        kw.update(dims=dims, device=dev.device_kind, platform=dev.platform)
        found.append(kw)
        print(json.dumps(kw), flush=True)

    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    x = jax.jit(lambda k: 8.0 + jax.random.normal(k, dims, jnp.float32))(key)
    ghost = jnp.zeros((1,) + dims[1:], jnp.float32)
    Ll = Laplacian(dims, axes=(0, 1, 2), weights=(1, 1, 1),
                   sampling=(1, 1, 1), dtype=np.float32)
    want = {False: jax.jit(lambda v: Ll._matvec(v.ravel()).reshape(dims))(x),
            True: jax.jit(lambda v: Ll._rmatvec(v.ravel()).reshape(dims))(x)}
    norm = {a: float(jnp.max(jnp.abs(w))) for a, w in want.items()}

    def err(y, adj):
        return float(jnp.max(jnp.abs(y.reshape(dims) - want[adj]))) / norm[adj]

    def row(form, fns, **kw):
        out = dict(form=form, **kw)
        for adj, name in ((False, "forward"), (True, "adjoint")):
            try:
                t0 = time.perf_counter()
                y = jax.block_until_ready(fns[adj](x))
                out[f"{name}_first_s"] = round(time.perf_counter() - t0, 2)
                out[f"{name}_err"] = err(y, adj)
                del y
                out[f"{name}_ms"] = best(fns[adj], x)
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                out[f"{name}_failed"] = f"{type(e).__name__}: {e}"[-600:]
        say(**out)

    if "slices" in forms:
        row("slices",
            {False: jax.jit(lambda v: Ll._matvec(v.ravel()).reshape(dims)),
             True: jax.jit(lambda v: Ll._rmatvec(v.ravel()).reshape(dims))})
    for R in strips:
        if "ring" in forms:
            pk._LAP_STRIPS = (R,)
            row("ring", {adj: jax.jit(partial(
                lambda v, a: pk.laplacian_stencil(v, ghost, ghost, 0, dims[0],
                                                  coef, adjoint=a), a=adj))
                for adj in (False, True)}, strip=R)
        if "three" in forms:
            row("three", {adj: jax.jit(partial(
                lambda v, a, r: three_operand(v, dims[0], coef, a, r),
                a=adj, r=R)) for adj in (False, True)}, strip=R)
    if "operator" in forms:
        pk._LAP_STRIPS = (strips[-1],)
        mesh = make_mesh(1)
        L = pmt.MPILaplacian(dims, axes=(0, 1, 2), weights=(1, 1, 1),
                             sampling=(1, 1, 1), mesh=mesh, dtype=np.float32)
        xd = pmt.DistributedArray.to_dist(x.ravel(), mesh=mesh)
        why = L._kernel_refusal(xd)
        fns = {False: jax.jit(lambda v: L.matvec(v)),
               True: jax.jit(lambda v: L.rmatvec(v))}
        out = dict(form="operator", took="slices:" + why if why
                   else "pmt_laplacian", strip=strips[-1])
        for adj, name in ((False, "forward"), (True, "adjoint")):
            y = jax.block_until_ready(fns[adj](xd))
            out[f"{name}_err"] = err(y._arr, adj)
            del y
            out[f"{name}_ms"] = best(fns[adj], xd)
        say(**out)

    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr33"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr33",
                           "laplacian_probe.json"), "w") as f:
        json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
