#!/usr/bin/env python3
"""Hand-run probe behind ``pmt_kirchhoff``'s pair groups: the
forward of ``TravelTimeSpray`` (``pallas_kernels.kirchhoff_spray``) at
``G`` traces a grid step against the one-trace kernel it replaces, on
the ``lsm_kirchhoff`` cell's tables (256 receivers a shot, 512 x 1,024
pixels in 32 x 32 blocks, 1,024 samples, two taps, float32), on the
chip:

    python3 chip_probe/kirchhoff_spray_probe.py [--ns 8 --nr 256 --nz 512 --nx 1024 --nt 1024]
    python3 chip_probe/kirchhoff_spray_probe.py --anywhere --ns 1 --nr 8 \\
        --nz 64 --nx 64 --nt 256              # rehearses the script on the CPU

Forms, each one forward apply under ``jax.jit`` (the operator's tables
as arguments), median of ``--reps`` timed calls after a warm one, each
ended by ``block_until_ready``:

- ``one_pair``: the kernel as it was before the pair groups
  (:func:`one_pair_spray`: one trace a grid step, each sample of a
  tile's band a read-modify-write of the trace's one accumulator);
- ``g<G>``: the program's kernel at ``G`` traces a grid step
  (``--groups``; each where ``G`` divides the pairs and its
  accumulators fit the spray's VMEM share), a tile walking the union of
  the ``G`` bands with every row of a loop step loaded before any is
  stored; ``g1`` is that loop order alone;
- ``program``: ``kirchhoff_spray`` as the operator calls it (the rule's
  ``G``).

Each form's traces against ``one_pair``'s: the largest absolute
difference (0 expected: the same values added in the same order, and
``+0.0`` outside a trace's own band), and the ``walk`` of each ``G``:
the samples its union bands walk over those of the pairs' own bands.
The last line is one JSON object; also written to
``chiprun_out/kirchhoff_spray_probe.<platform>.ns<ns>.nt<nt>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial, reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402  (initialises no backend)
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from pylops_mpi_tpu.ops import pallas_kernels as pk  # noqa: E402


def _one_pair_kernel(lh_ref, i_ref, w_ref, m_ref, y_ref, acc_ref, *,
                     taps: int, ntp: int):
    """``pallas_kernels._kirchhoff_spray_kernel`` at one trace a grid
    step, as it was before the pair groups."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(k, carry):
        i, w, m = i_ref[0, k], w_ref[0, k], m_ref[k]
        a = w * m
        b = (1 - w) * m
        lo = lh_ref[0, k]

        def step(s, c):
            for q in range(pk._KIR_UNROLL):
                t = lo + s * pk._KIR_UNROLL + q
                if taps == 2:
                    v = jnp.where(i == t, b, 0) + jnp.where(i == t - 1, a, 0)
                else:
                    v = jnp.where(i == t, a, 0)
                acc_ref[t] = acc_ref[t] + v
            return c
        return jax.lax.fori_loop(
            0, pk._kir_steps(lo, lh_ref[1, k] + (taps - 1)), step, carry)
    jax.lax.fori_loop(0, i_ref.shape[1], tile, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ys = jnp.sum(acc_ref[0:ntp], axis=1)
        lanes = (((1,), (1,)), ((), ()))
        if ys.dtype == jnp.float32:
            ones = jnp.ones((8, 128), jnp.bfloat16)
            y = reduce(jnp.add, (jax.lax.dot_general(
                ones, p, lanes, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
                for p in reversed(pk._bf16_parts(ys, 3))))
        else:
            y = jax.lax.dot_general(jnp.ones((8, 128), ys.dtype), ys, lanes)
        y_ref[0] = y[0:1]


@partial(jax.jit, static_argnames=("nt", "taps"))
def one_pair_spray(lohi, it, wt, m, nt: int, taps: int) -> jax.Array:
    """``pallas_kernels.kirchhoff_spray`` before the pair groups: the
    oracle they are held to, bit for bit."""
    pairs, nblk, _, tb = lohi.shape
    ntp = -(-nt // 128) * 128
    bands, ti, tw = pk._kir_specs(lohi, swap=False)
    y = pl.pallas_call(
        partial(_one_pair_kernel, taps=taps, ntp=ntp),
        grid=(pairs, nblk),
        in_specs=[bands, ti, tw,
                  pl.BlockSpec((tb, 8, 128), lambda p, j: (j, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, ntp), lambda p, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((pairs, 1, ntp), m.dtype),
        scratch_shapes=[pltpu.VMEM((ntp + pk._KIR_UNROLL, 8, 128), m.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=pk._VMEM_LIMIT_BYTES),
        interpret=pk._interpret(),
        name="pmt_kirchhoff_one_pair",
    )(lohi, it, wt, m.reshape(nblk * tb, 8, 128))
    return y[:, 0, :nt]


grouped_spray = jax.jit(pk._spray_call,
                        static_argnames=("nt", "taps", "group"))


def group_fits(pairs: int, nt: int, dtype, group: int) -> bool:
    """Whether ``kirchhoff_group``'s rule admits ``group`` (its cap
    aside)."""
    kept = pk._KIR_GROUPS
    pk._KIR_GROUPS = (group, 1)
    try:
        return pk.kirchhoff_group(pairs, nt, dtype) == group
    finally:
        pk._KIR_GROUPS = kept


def walk(lo, hi, taps: int, group: int) -> float:
    """Samples walked at ``group`` traces a step over those of the
    pairs' own bands (``lo``, ``hi``: ``(pairs, tiles)``)."""
    def steps(a, b):
        return np.maximum(b + (taps - 1) - a + pk._KIR_UNROLL, 0) \
            // pk._KIR_UNROLL
    glo = lo.reshape(-1, group, lo.shape[1]).min(1)
    ghi = hi.reshape(-1, group, hi.shape[1]).max(1)
    return float(group * steps(glo, ghi).sum() / steps(lo, hi).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for k, v in (("ns", 8), ("nr", 256), ("nz", 512), ("nx", 1024),
                 ("nt", 1024), ("reps", 7)):
        ap.add_argument("--" + k, type=int, default=v)
    ap.add_argument("--groups", default="1,2,4,8",
                    help="comma list of traces a grid step to try")
    ap.add_argument("--anywhere", action="store_true",
                    help="run without a TPU (a rehearsal of the script)")
    a = ap.parse_args(argv)

    if jax.default_backend() != "tpu" and not a.anywhere:
        print(f"kirchhoff_spray_probe: needs a TPU, found "
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
    lo = bands[:, :, 0].reshape(bands.shape[0], -1)
    hi = bands[:, :, 1].reshape(bands.shape[0], -1)
    live = lo <= hi
    pairs = int(spray.dimsd[0])
    groups = [g for g in (int(v) for v in a.groups.split(",") if v)
              if pairs % g == 0]
    rows = {"device": jax.devices()[0].device_kind, "sizes": sizes,
            "build_s": time.perf_counter() - t0, "pairs": pairs,
            "tiles": int(live.size), "tiles_nonempty": int(live.sum()),
            "band_mean": float((hi - lo + 1)[live].mean()),
            "band_max": int((hi - lo + 1)[live].max()),
            "rule_group": pk.kirchhoff_group(pairs, a.nt, np.float32),
            "walk": {str(g): walk(lo, hi, spray.taps, g) for g in groups},
            "table_bytes": spray.table_bytes, "forms": {}}
    print(json.dumps(rows), file=sys.stderr, flush=True)

    m = jax.random.normal(jax.random.key(1), (it.shape[1] * 1024,),
                          jnp.float32)
    forms = {"one_pair": partial(one_pair_spray, lohi, it, wt, m, a.nt,
                                 spray.taps)}
    for g in groups:
        if group_fits(pairs, a.nt, np.float32, g):
            forms[f"g{g}"] = partial(grouped_spray, lohi, it, wt, m, nt=a.nt,
                                     taps=spray.taps, group=g)
        else:
            rows["forms"][f"g{g}"] = {"error": "VMEM share"}
    forms["program"] = partial(pk.kirchhoff_spray, lohi, it, wt, m, a.nt,
                               spray.taps)
    traces = {}
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
        traces[name] = np.asarray(out)
        print(f"[probe] {name}: {rows['forms'][name]['ms']:.3f} ms "
              f"(first call {first:.1f} s)", file=sys.stderr, flush=True)
    base = traces["one_pair"]
    for name, y in traces.items():
        rows["forms"][name]["max_abs_diff"] = float(np.max(np.abs(y - base)))
        rows["forms"][name]["bitwise_equal"] = bool(np.array_equal(y, base))

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kirchhoff_spray_probe.%s.ns%d.nt%d.json"
                           % (jax.default_backend(), a.ns, a.nt)), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
