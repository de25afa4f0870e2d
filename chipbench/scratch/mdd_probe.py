#!/usr/bin/env python3
"""Hand-run probe of ``mdd_obc``'s two mechanisms alone, at the cell's
shapes, on the chip (not a cell; PERF.md section 6, PR 34 cites its
rows; the engine rule of ``ops/mdc.py`` rests on them):

    python3 chipbench/scratch/mdd_probe.py [--nf 64 --ns 4096 ...]
    python3 chipbench/scratch/mdd_probe.py --anywhere --nf 4 --ns 64 \\
        --nr 64 --nt 65 --nv 4          # rehearses the script on the CPU

One apply forward and adjoint, median of ``--reps`` timed calls after a
warm one, each ended by ``block_until_ready``:

- ``MPIFredholm1`` alone on complex spectra (the ``complex`` engine's
  product: two real ``einsum`` s on the stored planes, each plane read
  once) and with ``planar=True`` (plane-pair vectors, the same
  contraction); XLA's own complex ``einsum`` on the joined planes (what
  the plain reference runs: three real products, each plane read
  twice) beside them; bytes an apply = the kernel once + the spectra;
- ``local.FFT`` alone on the model-sized vector, complex (``jnp.fft``)
  against ``planes=True`` (the GEMM DFT on plane pairs);
- the whole ``MPIMDC`` apply, ``engine="complex"`` against
  ``engine="planar"``;
- what a TRUNCATED real DFT as one product would cost (not in the
  program; PERF.md section 7 sizes a later ``perf_opt`` issue from it):
  the model-sized vector ``(nt, nr * nv)`` against a ``(nt, 2 nfmax)``
  matrix of cosines and sines — only the chip's ``nfmax`` bins, the
  shift and the scaling folded into the matrix — and its adjoint;
- ``accuracy`` (small arrays, no timing): the device's ``rfft`` /
  ``irfft`` of the cell's odd length against NumPy in float64, the dot
  test of ``local.FFT`` and of the whole ``MPIMDC`` (at ``ns`` = ``nr``
  = 256), and the plain reference's residual drop there — what says
  whether a drop read at full size is the family's or the device's.

The last line is one JSON object with every row; also written to
``chiprun_out/mdd_probe.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for k, v in (("nf", 64), ("ns", 4096), ("nr", 4096), ("nt", 1023),
                 ("nv", 16), ("reps", 5)):
        ap.add_argument("--" + k, type=int, default=v)
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (a rehearsal of the script)")
    ap.add_argument("--skip", default="", help="comma list of row groups "
                    "to leave out: fredholm,fft,mdc,trunc,accuracy")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not a.anywhere:
        print(f"mdd_probe: needs a TPU, found {jax.default_backend()}",
              file=sys.stderr)
        return 2
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops import local
    from chipbench.builders import mdd as B

    mesh = pmt.make_mesh(1)
    pmt.set_default_mesh(mesh)
    sizes = {"nfmax": a.nf, "ns": a.ns, "nr": a.nr, "nt": a.nt, "nv": a.nv,
             "dt": 0.004, "dr": 12.5, "f0": 20.0, "sigma": 0.25,
             "tau_max": 0.2}
    key = jax.random.key(7)
    P = jax.block_until_ready(B.make_kernel(sizes)(key))
    kernel = 8 * a.nf * a.ns * a.nr
    skip = set(a.skip.split(","))
    rows = {"shapes": sizes, "kernel_bytes": kernel,
            "device": jax.devices()[0].device_kind}

    def timed(name, fn, *args, bytes_=None):
        f = jax.jit(fn)
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(f(*args))
        except Exception as e:           # out of memory: a row all the same
            rows[name] = {"error": str(e).split("\n")[0][:300]}
            print(f"[probe] {name}: FAILED {rows[name]['error']}",
                  file=sys.stderr, flush=True)
            return
        first = time.perf_counter() - t0
        ts = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        ms = 1e3 * statistics.median(ts)
        row = {"ms": ms, "first_s": first}
        if bytes_:
            row["bytes"] = bytes_
            row["GB_per_s"] = bytes_ / ms / 1e6
        rows[name] = row
        print(f"[probe] {name}: {ms:.3f} ms"
              + (f", {row['GB_per_s']:.0f} GB/s of {bytes_} bytes"
                 if bytes_ else "") + f" (first call {first:.1f} s)",
              file=sys.stderr, flush=True)

    def vec(n, dtype, k):
        v = jax.random.normal(jax.random.fold_in(key, k), (n,), jnp.float32)
        if np.issubdtype(dtype, np.complexfloating):
            v = jax.lax.complex(v, v[::-1])
        out = pmt.DistributedArray(global_shape=n, mesh=mesh,
                                   partition=pmt.Partition.BROADCAST,
                                   dtype=dtype)
        out[:] = v
        return out

    nfr, nfs = a.nf * a.nr * a.nv, a.nf * a.ns * a.nv
    spectra = lambda n: kernel + 8 * n + 8 * (nfr + nfs - n)
    if "fredholm" not in skip:
        Fr = pmt.MPIFredholm1(P, a.nv, dtype=np.complex64)
        assert Fr.G.unsafe_buffer_pointer() == P.unsafe_buffer_pointer()
        m, d = vec(nfr, np.complex64, 1), vec(nfs, np.complex64, 2)
        timed("fredholm.complex.matvec", lambda op, v: op.matvec(v).array,
              Fr, m, bytes_=spectra(nfr))
        timed("fredholm.complex.rmatvec", lambda op, v: op.rmatvec(v).array,
              Fr, d, bytes_=spectra(nfs))
        Fp = pmt.MPIFredholm1(P, a.nv, dtype=np.float32, planar=True)
        mp, dp = vec(2 * nfr, np.float32, 3), vec(2 * nfs, np.float32, 4)
        timed("fredholm.planar.matvec", lambda op, v: op.matvec(v).array,
              Fp, mp, bytes_=spectra(nfr))
        timed("fredholm.planar.rmatvec", lambda op, v: op.rmatvec(v).array,
              Fp, dp, bytes_=spectra(nfs))
        # XLA's own complex product on the joined planes (the plain
        # reference's): each plane is read twice
        timed("xla_complex_einsum.matvec",
              lambda p, v: B._product(p, v, "fsr,frv->fsv"), P,
              m.array.reshape(a.nf, a.nr, a.nv),
              bytes_=2 * kernel + 8 * (nfr + nfs))
        del Fr, Fp, m, d, mp, dp
    if "fft" not in skip:
        n = a.nt * a.nr * a.nv
        nfft = a.nt // 2 + 1
        x = jax.random.normal(key, (n,), jnp.float32)
        Fc = local.FFT((a.nt, a.nr, a.nv), axis=0, real=True,
                       ifftshift_before=True, dtype=np.float32)
        s = jax.block_until_ready(jax.jit(Fc.matvec)(x))
        timed("fft.complex.matvec", Fc.matvec, x, bytes_=4 * n + 8 * s.size)
        timed("fft.complex.rmatvec", Fc.rmatvec, s, bytes_=4 * n + 8 * s.size)
        Fq = local.FFT((a.nt, a.nr, a.nv), axis=0, real=True,
                       ifftshift_before=True, dtype=np.float32, planes=True)
        sp = jax.block_until_ready(jax.jit(Fq.matvec)(x))
        timed("fft.planes.matvec", Fq.matvec, x, bytes_=4 * n + 4 * sp.size)
        timed("fft.planes.rmatvec", Fq.rmatvec, sp,
              bytes_=4 * n + 4 * sp.size)
        del x, s, sp
    if "mdc" not in skip:
        for engine in ("complex", "planar"):
            Op = pmt.MPIMDC(P, nt=a.nt, nv=a.nv, dt=0.004, dr=12.5,
                            twosided=True, engine=engine)
            m = vec(Op.shape[1], np.float32, 5)
            d = vec(Op.shape[0], np.float32, 6)
            timed(f"mdc.{engine}.matvec", lambda op, v: op.matvec(v).array,
                  Op, m, bytes_=kernel + 4 * (Op.shape[0] + Op.shape[1]))
            timed(f"mdc.{engine}.rmatvec", lambda op, v: op.rmatvec(v).array,
                  Op, d, bytes_=kernel + 4 * (Op.shape[0] + Op.shape[1]))
            del Op, m, d
    if "trunc" not in skip:
        N = a.nr * a.nv
        k = np.arange(a.nf)[None, :] * np.arange(a.nt)[:, None]
        C = jnp.asarray(np.concatenate(
            [np.cos(2 * np.pi * k / a.nt), -np.sin(2 * np.pi * k / a.nt)],
            1) / np.sqrt(a.nt), jnp.float32)             # (nt, 2 nf)
        x = jax.random.normal(key, (a.nt * N,), jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        fwd = lambda c, v: jnp.einsum("tk,tn->kn", c, v.reshape(a.nt, N),
                                      precision=hi).ravel()
        adj = lambda c, y: jnp.einsum("tk,kn->tn", c,
                                      y.reshape(2 * a.nf, N),
                                      precision=hi).ravel()
        y = jax.block_until_ready(jax.jit(fwd)(C, x))
        timed("truncated_dft.matvec", fwd, C, x,
              bytes_=4 * (x.size + y.size))
        timed("truncated_dft.rmatvec", adj, C, y,
              bytes_=4 * (x.size + y.size))
        del x, y
    if "accuracy" not in skip:
        rel = lambda got, want: float(
            np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want))
        xs = np.random.default_rng(0).standard_normal((a.nt, 512))
        acc = {"rfft_vs_numpy": rel(jnp.fft.rfft(jnp.asarray(
            xs, jnp.float32), axis=0, norm="ortho"),
            np.fft.rfft(xs, axis=0, norm="ortho"))}
        ys = np.fft.rfft(xs, axis=0, norm="ortho")
        acc["irfft_vs_numpy"] = rel(jnp.fft.irfft(jnp.asarray(
            ys, jnp.complex64), n=a.nt, axis=0, norm="ortho"),
            np.fft.irfft(ys, n=a.nt, axis=0, norm="ortho"))
        small = dict(sizes, ns=256, nr=256, nv=4, events=6, noise=0.05)
        Ps = B.make_kernel(small)(key)
        Ops = pmt.MPIMDC(Ps, nt=a.nt, nv=4, dt=0.004, dr=12.5,
                         twosided=True)
        Fs = local.FFT((a.nt, 256, 4), axis=0, real=True,
                       ifftshift_before=True, dtype=np.float32)
        x = jax.random.normal(key, (Ops.shape[1],), jnp.float32)
        u = jax.random.normal(jax.random.fold_in(key, 9), (Ops.shape[0],),
                              jnp.float32)

        def dot_test(fwd, adjoint, x, u):
            y, z = fwd(x), adjoint(u)
            lhs, rhs = jnp.real(jnp.vdot(u, y)), jnp.real(jnp.vdot(z, x))
            return float(jnp.abs(lhs - rhs)
                         / (jnp.linalg.norm(u) * jnp.linalg.norm(y)))

        def wrap(v):
            out = pmt.DistributedArray(global_shape=v.size, mesh=mesh,
                                       partition=pmt.Partition.BROADCAST,
                                       dtype=np.float32)
            out[:] = v
            return out

        acc["mdc_dot_test"] = dot_test(
            lambda v: Ops.matvec(wrap(v)).array,
            lambda v: Ops.rmatvec(wrap(v)).array, x, u)
        s_ = Fs.matvec(x)
        us = jax.lax.complex(u[:s_.size], u[-s_.size:])
        acc["fft_dot_test"] = dot_test(Fs.matvec, Fs.rmatvec, x, us)
        mv, rmv = B.plain_system(small)
        with jax.default_matmul_precision("highest"):
            acc["plain_dot_test"] = dot_test(
                lambda v: mv(Ps, v.reshape(a.nt, 256, 4)).ravel(),
                lambda v: rmv(Ps, v.reshape(a.nt, 256, 4)).ravel(), x, u)
            d = mv(Ps, B.make_response(small)(key))
            xr, drop = B.plain_solve(small, 30)(Ps, d)
        acc["plain_drop_ns256"] = float(drop)
        xp = pmt.cgls(Ops, wrap(d.ravel()), x0=wrap(jnp.zeros_like(x)),
                      niter=30, tol=0.0)[0]
        acc["program_vs_plain_ns256"] = rel(xp.array, np.asarray(xr).ravel())
        rows["accuracy"] = acc
        print(f"[probe] accuracy: {acc}", file=sys.stderr, flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    rows["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "mdd_probe.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
