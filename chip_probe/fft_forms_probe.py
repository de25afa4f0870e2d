#!/usr/bin/env python3
"""Hand-run probe behind ``ops/local.py::truncated_dft_pays`` (PR 35):
``local.FFT(nfkeep=K)`` in its two forms — ONE real matrix product
against ``(nt, 2K)`` cosines and sines, or ``jnp.fft`` with the cut
(forward) and the zero pad (adjoint) beside it — at ``mdd_obc``'s
``N`` = 65,536 traces, on the chip:

    python3 chip_probe/fft_forms_probe.py
    python3 chip_probe/fft_forms_probe.py --traces 16384 --cases \\
        4095:64,4095:512,4095:2047,4096:64,4096:512,4096:2048
    python3 chip_probe/fft_forms_probe.py --anywhere --traces 256 \\
        --cases 65:8,65:32,64:8,64:32      # rehearses the script on the CPU

Each row is one ``nt:nfkeep`` of ``--cases``: the operator built as
``MPIMDC`` builds it (``(nt, traces / 16, 16)``, axis 0, the shift on
odd ``nt``), each form forced through the rule it asks, one apply
forward and one adjoint under ``jax.jit``, median of ``--reps`` timed
calls after a warm one, each ended by ``block_until_ready``. The first
case is also run with ``planes=True``, and held to NumPy's float64
``rfft`` on 512 traces (``accuracy``: both forms forward and adjoint,
relative 2-norm). The last line is one JSON object with every row;
also written to ``chiprun_out/fft_forms_probe.<platform>.json`` (a
rehearsal does not overwrite the chip's rows).
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

CASES = ("1023:64,1023:128,1023:256,1023:511,"
         "1024:64,1024:128,1024:256,1024:512,"
         "1000:64,1000:256,1022:64,1022:256")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=CASES,
                    help="comma list of nt:nfkeep")
    ap.add_argument("--traces", type=int, default=65536)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (a rehearsal of the script)")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not a.anywhere:
        print(f"fft_forms_probe: needs a TPU, found {jax.default_backend()}",
              file=sys.stderr)
        return 2
    import pylops_mpi_tpu  # noqa: F401  (pins the matmul precision)
    from pylops_mpi_tpu.ops import local

    key = jax.random.key(11)
    rows = {"traces": a.traces, "device": jax.devices()[0].device_kind,
            "matmul_precision": str(jax.config.jax_default_matmul_precision),
            "rule": {}}
    rule = local.truncated_dft_pays

    def build(nt, nfkeep, form, traces, **kw):
        """``local.FFT`` as ``MPIMDC`` builds it, in the asked form."""
        local.truncated_dft_pays = lambda *_: form == "product"
        try:
            return local.FFT((nt, traces // 16, 16), axis=0, real=True,
                             ifftshift_before=bool(nt % 2),
                             dtype=np.float32, nfkeep=nfkeep, **kw)
        finally:
            local.truncated_dft_pays = rule

    def timed(name, fn, x):
        f = jax.jit(fn)
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        first = time.perf_counter() - t0
        ts = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        rows[name] = {"ms": 1e3 * statistics.median(ts),
                      "min_ms": 1e3 * min(ts), "first_s": first}
        print(f"[probe] {name}: {rows[name]['ms']:.3f} ms "
              f"(first call {first:.1f} s)", file=sys.stderr, flush=True)

    cases = [tuple(int(v) for v in c.split(":"))
             for c in a.cases.split(",")]
    for i, (nt, nfkeep) in enumerate(cases):
        rows["rule"][f"{nt}:{nfkeep}"] = bool(rule(nt, nfkeep))
        x = jax.random.normal(key, (nt * a.traces,), jnp.float32)
        for kw in ({}, {"planes": True})[:2 if i == 0 else 1]:
            tag = f"nt{nt}.keep{nfkeep}" + (".planes" if kw else "")
            for form in ("product", "fft"):
                op = build(nt, nfkeep, form, a.traces, **kw)
                assert (op._why is None) == (form == "product"), op._why
                s = jax.block_until_ready(jax.jit(op.matvec)(x))
                timed(f"{tag}.{form}.matvec", op.matvec, x)
                timed(f"{tag}.{form}.rmatvec", op.rmatvec, s)
                del s
        del x

    # both forms against NumPy in float64, on few traces
    nt, nfkeep = cases[0]
    small = 512
    xs = np.random.default_rng(0).standard_normal((nt, small))
    shifted = np.fft.ifftshift(xs, axes=0) if nt % 2 else xs
    scale = np.ones((nt // 2 + 1, 1))
    scale[1:(nt + 1) // 2] = np.sqrt(2.0)
    want = (np.fft.rfft(shifted, axis=0, norm="ortho") * scale)[:nfkeep]
    u = want[::-1] * (1 + 0.5j)
    back = np.fft.irfft(np.pad(u, ((0, nt // 2 + 1 - nfkeep), (0, 0)))
                        / scale, n=nt, axis=0, norm="ortho")
    back = np.fft.fftshift(back, axes=0) if nt % 2 else back
    rel = lambda got, ref: float(np.linalg.norm(np.asarray(got).ravel()
                                                - ref.ravel())
                                 / np.linalg.norm(ref))
    acc = {}
    for form in ("product", "fft"):
        op = build(nt, nfkeep, form, small)
        acc[f"{form}.matvec"] = rel(op.matvec(jnp.asarray(
            xs.ravel(), jnp.float32)), want)
        acc[f"{form}.rmatvec"] = rel(op.rmatvec(jnp.asarray(
            u.ravel(), jnp.complex64)), back)
    rows["accuracy"] = acc
    print(f"[probe] accuracy: {acc}", file=sys.stderr, flush=True)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"fft_forms_probe.{jax.default_backend()}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
