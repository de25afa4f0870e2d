#!/usr/bin/env python3
"""Compile the ``mdd_obc`` cell's programs for ONE chip of a DESCRIBED
v5e:2x2 topology, here, without the chip (on-chip-measurement guide,
section 2, rehearsal 3). A scratch script run by hand, never imported:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/compile_mdd_topology.py \
        [--what solver|fredholm|fft|reference|family|control] [--nf 64 --ns 4096 ...]

``solver``: the program ``pmt.cgls`` runs for ``pmt.MPIMDC`` (the fused
two-sweep CGLS loop, 30 iterations, a caller's zero ``x0``), the
operator built INSIDE the traced function from an abstract kernel (an
8.59 GB kernel cannot be made here; in the real program it enters as a
pytree argument, an entry parameter all the same). ``fredholm`` /
``fft``: one forward and one adjoint apply of ``MPIFredholm1`` /
``local.FFT`` alone. ``reference``: the builder's plain solve;
``family``: its kernel generator; ``control``: its bfloat16-product
solve. Prints
``memory_analysis()`` in kernels and in vectors, and every instruction
of the compiled program, outside its fusions, whose result is at least a quarter of the
kernel (a kernel-sized temporary: there may be none), or a vector or
more and no fusion (a ``copy`` or ``transpose`` there is a pass the
algebra did not ask for). A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

ITEM = {"f32": 4, "c64": 8, "bf16": 2, "s32": 4, "pred": 1, "u32": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--what", default="solver",
                    choices=("solver", "fredholm", "fft", "reference",
                             "family", "control"))
    ap.add_argument("--nf", type=int, default=64)
    ap.add_argument("--ns", type=int, default=4096)
    ap.add_argument("--nr", type=int, default=4096)
    ap.add_argument("--nt", type=int, default=1023)
    ap.add_argument("--nv", type=int, default=16)
    ap.add_argument("--niter", type=int, default=30)
    ap.add_argument("--engine", default=None)
    ap.add_argument("--complex", action="store_true",
                    help="hand the kernel over as complex64, not planes")
    ap.add_argument("--dump", default=None, help="write the HLO here")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("sp",))
    rep = NamedSharding(mesh, P())

    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.distributedarray import DistributedArray
    from pylops_mpi_tpu.ops import local
    from pylops_mpi_tpu.solvers import basic

    kernel = 8 * a.nf * a.ns * a.nr
    nm, nd = a.nt * a.nr * a.nv, a.nt * a.ns * a.nv
    vec = 4 * nd
    # the kernel as the (re, im) plane pair the operator stores; with
    # --complex as a complex64 array (XLA splits it at the entry)
    G = jax.ShapeDtypeStruct((a.nf, a.ns, a.nr), jnp.complex64,
                             sharding=NamedSharding(mesh, P("sp"))) \
        if a.complex else jax.ShapeDtypeStruct(
            (2, a.nf, a.ns, a.nr), jnp.float32,
            sharding=NamedSharding(mesh, P(None, "sp")))

    def vector(n, dtype=jnp.float32):
        aux = (mesh, pmt.Partition.BROADCAST, 0, (n,),
               pmt.local_split((n,), 1, pmt.Partition.BROADCAST, 0), None)
        return DistributedArray.tree_unflatten(aux, [jax.ShapeDtypeStruct(
            (n,), dtype, sharding=rep)])

    def mdc(g):
        kw = {} if a.engine is None else {"engine": a.engine}
        return pmt.MPIMDC(g, nt=a.nt, nv=a.nv, dt=0.004, dr=1.0,
                          twosided=True, mesh=mesh, **kw)

    if a.what == "solver":
        fn = jax.jit(lambda g, y, x0, damp, tol: basic._cgls_fused(
            mdc(g), y, x0, damp, tol, niter=a.niter))
        args = (G, vector(nd), vector(nm), jnp.float32(0), jnp.float32(0))
    elif a.what == "fredholm":
        nfs, nfr = a.nf * a.ns * a.nv, a.nf * a.nr * a.nv

        def both(g, m, d):
            op = pmt.MPIFredholm1(g, a.nv, mesh=mesh, dtype=np.complex64)
            return op.matvec(m), op.rmatvec(d)
        fn = jax.jit(both)
        args = (G, vector(nfr, jnp.complex64), vector(nfs, jnp.complex64))
    elif a.what == "fft":
        op = local.FFT((a.nt, a.nr, a.nv), axis=0, real=True,
                       ifftshift_before=True, dtype=np.float32)
        fn = jax.jit(lambda m, s: (op.matvec(m), op.rmatvec(s)))
        nfft = a.nt // 2 + 1
        args = (jax.ShapeDtypeStruct((nm,), jnp.float32, sharding=rep),
                jax.ShapeDtypeStruct((nfft * a.nr * a.nv,), jnp.complex64,
                                     sharding=rep))
    else:
        from chipbench.builders import mdd as B
        sizes = {"nfmax": a.nf, "ns": a.ns, "nr": a.nr, "nt": a.nt,
                 "nv": a.nv, "dt": 0.004, "dr": 1.0, "f0": 20.0,
                 "sigma": 0.25, "tau_max": 0.2}
        if a.what == "family":         # the kernel's generator
            fn = B.make_kernel(sizes)
            args = (jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                         sharding=rep),)
        else:
            fn = B.plain_solve(sizes, a.niter, **(
                B.CONTROLS["bf16"] if a.what == "control" else {})).solve
            args = (G, jax.ShapeDtypeStruct((a.nt, a.ns, a.nv),
                                            jnp.float32, sharding=rep))

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    print(f"{a.what}: compiled for {topo.devices[0].device_kind} in "
          f"{time.perf_counter() - t0:.1f}s; the kernel is {kernel} "
          f"bytes, a vector {vec}")
    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)
    for unit, size in (("kernels", kernel), ("vectors", vec)):
        print("in %s: arguments %.2f, outputs %.2f, aliased %.2f, "
              "temporaries %.2f" % ((unit,) + tuple(
                  b / size for b in (ma.argument_size_in_bytes,
                                     ma.output_size_in_bytes,
                                     ma.alias_size_in_bytes,
                                     ma.temp_size_in_bytes))))
    hlo = compiled.as_text()
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(hlo)
    big, wide, fused = {}, {}, False
    for line in hlo.split("\n"):
        if line and not line.startswith(" "):
            # a computation's head: what is inside a fusion is no array
            fused = "fused_computation" in line \
                or "fusion" in line.split("(")[0]
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]+)\]\S* "
                     r"(\w[\w\-]*)\(", line)
        if not m or m.group(2) not in ITEM:
            continue
        size = ITEM[m.group(2)] * int(np.prod(
            [int(d) for d in m.group(3).split(",")]))
        kind = m.group(4)
        if fused or kind in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if size >= kernel // 4:
            big[f"{kind} {m.group(2)}[{m.group(3)}]"] = \
                big.get(f"{kind} {m.group(2)}[{m.group(3)}]", 0) + 1
        elif size >= vec and kind not in ("fusion", "custom-call"):
            wide[kind] = wide.get(kind, 0) + 1
    print("instructions whose result is a quarter of the kernel or more:",
          big or "none")
    print("vector-sized instructions that are no fusion:", wide or "none")
    print("reads of the kernel parameter by fusions/convolutions: see "
          "--dump; ffts:", len(re.findall(r" fft\(", hlo)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
