#!/usr/bin/env python3
"""Hand-run probe behind ``pmt_kirchhoff_adj``'s lane gather (PR 39):
the adjoint of ``TravelTimeSpray`` (``pallas_kernels.kirchhoff_gather``)
against the band loop it replaces, on one shot of the
``lsm_kirchhoff`` cell (256 pairs, 512 x 1,024 pixels in 32 x 32
blocks, 1,024 samples, two taps, float32: the tables
``chipbench/scratch/lsm_probe.py`` times), on the chip:

    python3 chip_probe/kirchhoff_gather_probe.py [--ns 1 --nr 256 --nz 512 --nx 1024 --nt 1024]
    python3 chip_probe/kirchhoff_gather_probe.py --anywhere --ns 1 --nr 8 \\
        --nz 64 --nx 64 --nt 256              # rehearses the script on the CPU

Forms, each one adjoint apply under ``jax.jit`` (the operator's tables
as arguments), median of ``--reps`` timed calls after a warm one, each
ended by ``block_until_ready``:

- ``band_loop``: the kernel as it was before PR 39
  (:func:`band_loop_gather`: a compare and two selects a sample of
  each tile's band, the samples read as scalars from SMEM);
- ``windowed``: the program's kernel (one lane gather a tap from a
  128-sample window of the trace where the band fits it);
- ``windowed_g<G>``: the same kernel taking ``G`` tiles a step of its
  loop (``--groups``; layouts tried, not the program's);
- ``windowed_bcast``: the program's lane gather with its window read
  by one sublane-broadcast load (stride 0; the interpreter takes no
  such load, so on a TPU only, and where every tile is gathered);
- ``spray``: the forward ``pmt_kirchhoff`` beside them, for scale.

Each form's image against ``band_loop``'s: the largest absolute
difference (0 expected: the same values, summed in the same order).
The last line is one JSON object; also written to
``chiprun_out/kirchhoff_gather_probe.<platform>.ns<ns>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402  (initialises no backend)
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from pylops_mpi_tpu.ops import pallas_kernels as pk  # noqa: E402


def _band_loop_kernel(lh_ref, z_ref, i_ref, w_ref, m_ref, *, taps: int):
    """``pallas_kernels._kirchhoff_gather_kernel`` as PR 38 left it."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        m_ref[...] = jnp.zeros_like(m_ref)

    def tile(k, carry):
        i, w = i_ref[0, k], w_ref[0, k]
        lo = lh_ref[0, k]
        zero = jnp.zeros(i.shape, m_ref.dtype)

        def step(s, g):
            g0, g1 = g
            for q in range(pk._KIR_UNROLL):
                t = lo + s * pk._KIR_UNROLL + q
                here = i == t
                g0 = jnp.where(here, z_ref[0, t], g0)
                if taps == 2:
                    g1 = jnp.where(here, z_ref[0, t + 1], g1)
            return g0, g1
        g0, g1 = jax.lax.fori_loop(0, pk._kir_steps(lo, lh_ref[1, k]), step,
                                   (zero, zero))
        m_ref[k] = m_ref[k] + ((1 - w) * g0 + w * g1 if taps == 2
                             else w * g0)
        return carry
    jax.lax.fori_loop(0, i_ref.shape[1], tile, 0)


@partial(jax.jit, static_argnames=("taps",))
def band_loop_gather(lohi, it, wt, z, taps: int) -> jax.Array:
    """``pallas_kernels.kirchhoff_gather`` as PR 38 left it: the
    oracle the lane gather is held to, bit for bit."""
    pairs, nblk, _, tb = lohi.shape
    nt = z.shape[1]
    ntz = -(-(nt + pk._KIR_UNROLL + 1) // 128) * 128
    bands, ti, tw = pk._kir_specs(lohi, swap=True)
    m = pl.pallas_call(
        partial(_band_loop_kernel, taps=taps),
        grid=(nblk, pairs),
        in_specs=[bands,
                  pl.BlockSpec((None, 1, ntz), lambda j, p: (p, 0, 0),
                               memory_space=pltpu.SMEM),
                  ti, tw],
        out_specs=pl.BlockSpec((tb, 8, 128), lambda j, p: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * tb, 8, 128), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=pk._VMEM_LIMIT_BYTES),
        interpret=pk._interpret(),
        name="pmt_kirchhoff_adj_band_loop",
    )(lohi, jnp.pad(z, ((0, 0), (0, ntz - nt)))[:, None, :], it, wt)
    return m.ravel()


def grouped_gather(group: int):
    """The program's ``kirchhoff_gather`` traced with ``group`` tiles a
    step of its loop in place of ``_KIR_GROUP`` (a layout tried)."""
    def fn(lohi, it, wt, z, taps):
        kept = pk._KIR_GROUP
        pk._KIR_GROUP = group
        try:
            return pk.kirchhoff_gather.__wrapped__(lohi, it, wt, z, taps)
        finally:
            pk._KIR_GROUP = kept
    return jax.jit(fn, static_argnames=("taps",))


def _bcast_kernel(lh_ref, zw_ref, i_ref, w_ref, m_ref, *, group: int):
    """The program's kernel for two taps and tables whose every tile is
    gathered, its window read by ONE sublane-broadcast load (stride 0)
    in place of a load and a sublane replicate: compiled only."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        m_ref[...] = jnp.zeros_like(m_ref)

    def lanes(k):
        i = i_ref[0, k]
        b = jnp.minimum(lh_ref[0, k] >> pk._KIR_SHIFT, zw_ref.shape[0] - 1)
        row = zw_ref[pl.ds(b, i.shape[0], stride=0), :]
        d = i - b * pk._KIR_STRIDE
        live = d >= 0
        d = jnp.where(live, d, 0)
        return [jnp.where(live, jnp.take_along_axis(
            row, d + s, axis=1, mode="promise_in_bounds"), 0) for s in (0, 1)]

    def tiles(s, carry):
        ks = [s * group + u for u in range(group)]
        gs = [lanes(k) for k in ks]
        for k, (g0, g1) in zip(ks, gs):
            w = w_ref[0, k]
            m_ref[k] = m_ref[k] + ((1 - w) * g0 + w * g1)
        return carry
    jax.lax.fori_loop(0, i_ref.shape[1] // group, tiles, 0)


@jax.jit
def bcast_gather(lohi, it, wt, z) -> jax.Array:
    pairs, nblk, _, tb = lohi.shape
    nt = z.shape[1]
    nwin = -(-nt // pk._KIR_STRIDE)
    zs = jnp.pad(z, ((0, 0), (0, (nwin + 1) * pk._KIR_STRIDE - nt))
                 ).reshape(pairs, nwin + 1, pk._KIR_STRIDE)
    windows = jnp.concatenate([zs[:, :-1], zs[:, 1:]], axis=2)
    bands, ti, tw = pk._kir_specs(lohi, swap=True)
    m = pl.pallas_call(
        partial(_bcast_kernel, group=int(np.gcd(pk._KIR_GROUP, tb))),
        grid=(nblk, pairs),
        in_specs=[bands,
                  pl.BlockSpec((None, nwin, pk._KIR_WINDOW),
                               lambda j, p: (p, 0, 0)),
                  ti, tw],
        out_specs=pl.BlockSpec((tb, 8, 128), lambda j, p: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * tb, 8, 128), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=pk._VMEM_LIMIT_BYTES),
        name="pmt_kirchhoff_adj_bcast",
    )(lohi, windows, it, wt)
    return m.ravel()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for k, v in (("ns", 1), ("nr", 256), ("nz", 512), ("nx", 1024),
                 ("nt", 1024), ("reps", 7)):
        ap.add_argument("--" + k, type=int, default=v)
    ap.add_argument("--groups", default="4,16",
                    help="comma list of tiles a loop step to try besides "
                         "the program's")
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (a rehearsal of the script)")
    a = ap.parse_args(argv)

    if jax.default_backend() != "tpu" and not a.anywhere:
        print(f"kirchhoff_gather_probe: needs a TPU, found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    from pylops_mpi_tpu import aot
    from pylops_mpi_tpu.models import KirchhoffDemigration
    from chipbench.builders import lsm as B
    aot.maybe_enable_compile_cache(os.path.join(ROOT, ".jax_cache"))

    sizes = dict(B.DEFAULT_SIZES, ns=a.ns, nr=a.nr, nz=a.nz, nx=a.nx, nt=a.nt)
    t0 = time.perf_counter()
    spray = KirchhoffDemigration(*B.geometry(sizes).args,
                                 dtype=np.float32).A.B
    lohi, it, wt = jax.block_until_ready((spray._lohi, spray.itrav,
                                          spray.weight))
    bands = np.asarray(lohi)
    lo, hi = bands[:, :, 0], bands[:, :, 1]
    live = lo <= hi
    rows = {"device": jax.devices()[0].device_kind, "sizes": sizes,
            "build_s": time.perf_counter() - t0,
            "pairs": int(spray.dimsd[0]), "tiles": int(live.size),
            "tiles_nonempty": int(live.sum()),
            "tiles_windowed": int(np.asarray(pk.kirchhoff_windowed(
                lo, hi, spray.taps)).sum()),
            "band_mean": float((hi - lo + 1)[live].mean()),
            "band_max": int((hi - lo + 1)[live].max()),
            "table_bytes": spray.table_bytes, "forms": {}}
    print(json.dumps(rows), file=sys.stderr, flush=True)

    key = jax.random.key(0)
    z = jax.random.normal(key, spray.dimsd, jnp.float32)
    m = jax.random.normal(jax.random.fold_in(key, 1), (it.shape[1] * 1024,),
                          jnp.float32)
    forms = {
        "band_loop": lambda: band_loop_gather(lohi, it, wt, z, spray.taps),
        "windowed": lambda: pk.kirchhoff_gather(lohi, it, wt, z, spray.taps),
        "spray": lambda: pk.kirchhoff_spray(lohi, it, wt, m, a.nt,
                                            spray.taps),
    }
    if jax.default_backend() == "tpu" and spray.taps == 2 \
            and rows["tiles_windowed"] == rows["tiles"]:
        forms["windowed_bcast"] = partial(bcast_gather, lohi, it, wt, z)
    for g in (int(v) for v in a.groups.split(",") if v):
        forms[f"windowed_g{g}"] = partial(grouped_gather(g), lohi, it, wt, z,
                                          spray.taps)
    images = {}
    for name, fn in forms.items():
        try:
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            first = time.perf_counter() - t0
            ts = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
        except Exception as e:                      # keep the other rows
            rows["forms"][name] = {"error": f"{type(e).__name__}: {e}"[:400]}
            print(f"[probe] {name}: {rows['forms'][name]['error']}",
                  file=sys.stderr, flush=True)
            continue
        rows["forms"][name] = {"ms": 1e3 * statistics.median(ts),
                               "min_ms": 1e3 * min(ts), "first_s": first}
        if name != "spray":
            images[name] = np.asarray(out)
        print(f"[probe] {name}: {rows['forms'][name]['ms']:.3f} ms "
              f"(first call {first:.1f} s)", file=sys.stderr, flush=True)
    base = images["band_loop"]
    for name, img in images.items():
        rows["forms"][name]["max_abs_diff"] = float(np.max(np.abs(
            img - base)))
        rows["forms"][name]["bitwise_equal"] = bool(np.array_equal(img,
                                                                   base))

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kirchhoff_gather_probe.%s.ns%d.json"
                           % (jax.default_backend(), a.ns)), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
