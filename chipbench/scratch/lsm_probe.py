#!/usr/bin/env python3
"""Time the forms of the Kirchhoff spray and gather alone, on the chip:
the rows behind ``models/lsm.py::TravelTimeSpray._form``. A scratch
script run by hand through the chip tool, never imported:

    python3 chipbench/scratch/lsm_probe.py [--ns 1 --nr 256 --nz 512 --nx 1024 --nt 1024]
    python3 chipbench/scratch/lsm_probe.py --anywhere --ns 1 --nr 8 --nz 64 --nx 64 --nt 256

One batch of ``ns`` shots of the ``lsm_kirchhoff`` survey is built by
the program (``pmt.models.KirchhoffDemigration``: tables made on the
device), then ``TravelTimeSpray``'s two applies are timed as

- ``pmt_kirchhoff``: the Pallas kernels the operator takes;
- ``scatter``: the trace-by-trace scatter-add / gather it takes where
  the kernels are refused (steered here by answering "no" for
  ``pallas_kernels.kirchhoff_legal``, as a test would);
- ``plain``: the benchmark's plain oracle (``builders/lsm.py::
  plain_spray``: ``.at[].add`` and indexing in blocks of pairs, on the
  reference's OWN travel times);
- ``banded``: the form the benchmark's reference solves run
  (``banded_spray``: compares over each run's short band), which this
  script holds to ``plain`` on the chip,

the program's on the image in its block order (``spray * order``), the
benchmark's on the image in the reference's,

each after a warm-up, ``--reps`` times, with the largest difference
between the forms' results. Prints one JSON line and writes it to
``chiprun_out/lsm_probe.json``. Refuses without a TPU unless
``--anywhere`` (which proves the script, never a time).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ns", type=int, default=1)
    ap.add_argument("--nr", type=int, default=256)
    ap.add_argument("--nz", type=int, default=512)
    ap.add_argument("--nx", type=int, default=1024)
    ap.add_argument("--nt", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--forms", default="pmt_kirchhoff,scatter,plain,banded")
    ap.add_argument("--anywhere", action="store_true")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not a.anywhere:
        print(f"lsm_probe: platform {jax.default_backend()!r}, not a TPU",
              file=sys.stderr)
        return 2
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import aot
    from pylops_mpi_tpu.models import KirchhoffDemigration
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    from chipbench.builders import lsm as B
    aot.maybe_enable_compile_cache(os.path.join(ROOT, ".jax_cache"))

    sizes = dict(B.DEFAULT_SIZES, ns=a.ns, nr=a.nr, nz=a.nz, nx=a.nx, nt=a.nt)
    geo = B.geometry(sizes)
    t0 = time.perf_counter()
    K = KirchhoffDemigration(*geo.args, dtype=np.float32)
    spray, order = K.A.B, K.B
    jax.block_until_ready(spray.itrav)
    out = {"device": jax.devices()[0].device_kind, "sizes": sizes,
           "build_s": time.perf_counter() - t0, "band": spray.band,
           "dropped": spray.dropped, "table_bytes": spray.table_bytes,
           "pairs": spray.dimsd[0]}
    lohi = np.asarray(spray._lohi)
    span = np.maximum(lohi[:, :, 1] - lohi[:, :, 0] + 1, 0)
    out["band_mean"] = float(span.mean())
    print(json.dumps(out), flush=True)

    key = jax.random.key(0)
    m = jax.random.normal(key, (a.nz * a.nx,), jnp.float32)    # an image
    z = jax.random.normal(jax.random.fold_in(key, 1), (spray.shape[0],),
                          jnp.float32)
    both = spray * order
    times = jax.block_until_ready(B.point_times(sizes))

    def timed(f, *args):
        y = jax.block_until_ready(f(*args))          # compiles
        ts = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(1e3 * (time.perf_counter() - t0))
        return y, ts

    results, rows = {}, {}
    legal = pk.kirchhoff_legal
    for form in a.forms.split(","):
        try:
            if form in ("plain", "banded"):
                mv, rmv = B.plain_spray(sizes) if form == "plain" \
                    else B.banded_spray(sizes, B.band_width(sizes, times))
                fwd = jax.jit(lambda t, v: mv(t, B.to_blocks(
                    v.reshape(a.nz, a.nx), sizes)).ravel())
                adj = jax.jit(lambda t, v: B.from_blocks(rmv(
                    t, v.reshape(spray.dimsd)), sizes).ravel())
                y, tf = timed(fwd, times, m)
                g, ta = timed(adj, times, z)
            else:
                pk.kirchhoff_legal = legal if form == "pmt_kirchhoff" \
                    else (lambda *_: False)
                # the operator travels as an argument: its tables are
                # no constants of the program
                fwd = jax.jit(lambda op, v: op.matvec(v))
                adj = jax.jit(lambda op, v: op.rmatvec(v))
                y, tf = timed(fwd, both, m)
                g, ta = timed(adj, both, z)
            results[form] = (y, g)
            rows[form] = {"forward_ms": tf, "adjoint_ms": ta}
        except Exception as e:                      # keep the other rows
            rows[form] = {"error": f"{type(e).__name__}: {e}"[:400]}
        print(json.dumps({form: rows[form]}), flush=True)
    pk.kirchhoff_legal = legal
    base = results.get("plain") or next(iter(results.values()), None)
    err = jax.jit(lambda x, r: jnp.sqrt(jnp.sum((x - r) ** 2)
                                        / jnp.sum(r * r)))
    for form, (y, g) in results.items():
        rows[form]["forward_rel_to_%s" % ("plain" if "plain" in results
                                          else "first")] = float(
            err(y, base[0]))
        rows[form]["adjoint_rel"] = float(err(g, base[1]))
    # the dot test of the kernels, in float32
    if "pmt_kirchhoff" in results:
        y, g = results["pmt_kirchhoff"]
        out["dot"] = [float(jnp.vdot(y, z)), float(jnp.vdot(m, g))]
    out["rows"] = rows
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "lsm_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
