#!/usr/bin/env python3
"""Hand-run probe behind ``ops/pallas_kernels.py::plane_pair_cols_pay``:
``MPIFredholm1``'s products in the two forms ``normal_form`` chooses
between — ONE sweep of the kernel's plane pair by ``pmt_normal_planes``
(``normal_planes``), or the operator's ``matvec`` and ``rmatvec``, a
plane ``einsum`` sweep each, as a classic CGLS iteration runs them — at
``mdd_obc``'s kernel, 64 frequencies of 4,096 x 4,096 complex64 held as
float32 planes (8.59 GB), on the chip:

    python3 chip_probe/fredholm_normal_probe.py
    python3 chip_probe/fredholm_normal_probe.py --nv 16 --tiles 128,256
    python3 chip_probe/fredholm_normal_probe.py --anywhere --nf 4 \\
        --n 256 --nv 1,4 --tiles 64,128   # rehearses the script on the CPU

Each row is one ``nv`` (the spectra's columns; the kernel's forward
carries ``2 nv``): the operator's own products on its spectra, each
form, under ``jax.jit``, median of ``--reps`` timed calls after a warm
one, each ended by ``block_until_ready``; for the first ``nv``, also
the kernel alone at each row tile of ``--tiles`` on spectra already
laid as rows. ``err`` holds the one sweep's ``Q`` and ``Z2`` to the
pair's ``G v`` and ``Gᴴ s`` (relative 2-norm, the larger of the two).
The last line is one JSON object with every row; also written to
``chiprun_out/fredholm_normal_probe.<platform>.json`` (a rehearsal
does not overwrite the chip's rows).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nf", type=int, default=64)
    ap.add_argument("--n", type=int, default=4096, help="ns = nr")
    ap.add_argument("--nv", default="16,1,32", help="comma list")
    ap.add_argument("--tiles", default="128,256", help="comma list")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (a rehearsal of the script)")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not a.anywhere:
        print("fredholm_normal_probe: needs a TPU, found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops import pallas_kernels as pk

    nf, n = a.nf, a.n
    rows = {"nf": nf, "n": n, "device": jax.devices()[0].device_kind,
            "kernel_bytes": 8 * nf * n * n,
            "matmul_precision": str(jax.config.jax_default_matmul_precision),
            "forms": {}, "tiles": {}}
    key = jax.random.key(7)
    P = jax.block_until_ready(jax.jit(lambda k: jax.random.normal(
        k, (2, nf, n, n), jnp.float32) / np.float32(np.sqrt(n)))(key))
    mesh = pmt.make_mesh(1)

    def timed(f, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        first = time.perf_counter() - t0
        ts = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return out, {"ms": 1e3 * statistics.median(ts),
                     "min_ms": 1e3 * min(ts), "first_s": first}

    def rel(x, y):
        x, y = np.asarray(x, np.complex128), np.asarray(y, np.complex128)
        return float(np.linalg.norm(x - y) / np.linalg.norm(y))

    for i, nv in enumerate(int(v) for v in a.nv.split(",")):
        Fred = pmt.MPIFredholm1(P, nv, mesh=mesh, dtype=np.complex64)
        kv, ks = jax.random.split(jax.random.fold_in(key, nv))
        cplx = lambda k, m: jax.lax.complex(*jax.random.normal(
            k, (2, nf * m * nv), jnp.float32))
        v = pmt.DistributedArray(global_shape=nf * n * nv, mesh=mesh,
                                 partition=pmt.Partition.BROADCAST,
                                 dtype=np.complex64)
        s = pmt.DistributedArray(global_shape=nf * n * nv, mesh=mesh,
                                 partition=pmt.Partition.BROADCAST,
                                 dtype=np.complex64)
        v[:] = cplx(kv, n)
        s[:] = cplx(ks, n)
        out = {}
        forms = {"one_sweep": lambda op, v_, s_: tuple(
                     t.array for t in op.normal_planes(v_, s_)),
                 "pair": lambda op, v_, s_: (op.matvec(v_).array,
                                             op.rmatvec(s_).array)}
        for form, fn in forms.items():
            out[form], r = timed(jax.jit(fn), Fred, v, s)
            rows["forms"][f"nv{nv}.{form}"] = r
            print(f"[probe] nv {nv} {form}: {r['ms']:.3f} ms (first call "
                  f"{r['first_s']:.1f} s)", file=sys.stderr, flush=True)
        q, _, z2 = out["one_sweep"]
        err = max(rel(q, out["pair"][0]), rel(z2, out["pair"][1]))
        rows["forms"][f"nv{nv}"] = {
            "err": err, "rule": bool(pk.plane_pair_cols_pay(2 * nv)),
            "ratio": rows["forms"][f"nv{nv}.pair"]["ms"]
            / rows["forms"][f"nv{nv}.one_sweep"]["ms"]}
        ratio = rows["forms"][f"nv{nv}"]["ratio"]
        print(f"[probe] nv {nv}: pair / one {ratio:.3f}, err {err:.2e}",
              file=sys.stderr, flush=True)
        del out
        if i:
            continue
        nzp = -(-nv // pk.PLANE_PAIR_ROWS) * pk.PLANE_PAIR_ROWS
        C = jax.random.normal(kv, (nf, 2 * nzp, n), jnp.float32)
        S = jax.random.normal(ks, (nf, 2 * nzp, n), jnp.float32)
        for tm in (int(t) for t in a.tiles.split(",")):
            if n % tm:
                continue
            f = jax.jit(lambda P_, C_, S_, tm=tm: pk.plane_pair_normal(
                P_, C_, S_, tm=tm))
            _, r = timed(f, P, C, S)
            rows["tiles"][f"nv{nv}.tm{tm}"] = r
            print(f"[probe] kernel alone nv {nv} tm {tm}: {r['ms']:.3f} ms",
                  file=sys.stderr, flush=True)
        rows["tiles"]["rule_tile"] = pk.plane_pair_tile(P)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    plat = jax.default_backend()
    name = f"fredholm_normal_probe.{plat}.json"
    if plat != "tpu":
        name = f"fredholm_normal_probe.{plat}.rehearsal.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
