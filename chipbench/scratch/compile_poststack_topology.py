#!/usr/bin/env python3
"""Compile the ``poststack_3d`` cell's solver program for ONE chip of a
DESCRIBED v5e:2x2 topology, here, without the chip (on-chip-measurement
guide, section 2, rehearsal 3). A scratch script run by hand, never
imported:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/compile_poststack_topology.py \
        [--ny 192 --nx 1024 --nt0 1024]

It builds ``pmt.models.poststack_regularized`` over a one-device mesh of
the described chip, lowers the program ``pmt.cgls`` runs for it (the
fused two-sweep CGLS loop on the stacked system, 30 iterations, with an
``x0``) with abstract vectors, compiles it with the TPU compiler and
prints ``memory_analysis()`` in volumes, the kernels in it and every
instruction of the loop body whose result is a volume or more that is
not a fusion (a ``copy`` or ``transpose`` there is a pass over HBM the
algebra did not ask for).

One thing is steered here, in the script, because nothing runs and the
process sees the CPU: ``pallas_kernels._interpret`` answers "compiled",
as it does on a TPU, so ``Conv1D`` takes the form it takes there. A
compile that passes is not a chip run.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ny", type=int, default=192)
    ap.add_argument("--nx", type=int, default=1024)
    ap.add_argument("--nt0", type=int, default=1024)
    ap.add_argument("--niter", type=int, default=30)
    ap.add_argument("--interpret-form", action="store_true",
                    help="leave Conv1D on its jnp form (what a CPU takes)")
    ap.add_argument("--dump", default=None, help="write the HLO here")
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("sp",))

    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.models import poststack_regularized, ricker
    from pylops_mpi_tpu.ops import pallas_kernels
    from pylops_mpi_tpu.solvers import basic
    from pylops_mpi_tpu.distributedarray import DistributedArray
    from pylops_mpi_tpu.stacked import StackedDistributedArray
    if not args.interpret_form:
        pallas_kernels._interpret = lambda: False

    wav = ricker(np.arange(41) * 0.004, 15)[0].astype(np.float32)
    StackOp, Op, _ = poststack_regularized(
        wav, args.nt0, (args.ny, args.nx), 100.0, mesh=mesh,
        dtype=np.float32)
    V = args.ny * args.nx * args.nt0
    vol = 4 * V

    def abstract():
        tpl = DistributedArray.__new__(DistributedArray)
        del tpl
        aux = (mesh, pmt.Partition.SCATTER, 0, (V,),
               pmt.local_split((V,), 1, pmt.Partition.SCATTER, 0), None)
        return DistributedArray.tree_unflatten(aux, [jax.ShapeDtypeStruct(
            (V,), jnp.float32, sharding=NamedSharding(mesh, P("sp")))])

    # a caller's x0 is not donated (solvers/basic._run_cgls_fused)
    fn = jax.jit(lambda op, y, x0, damp, tol: basic._cgls_fused(
        op, y, x0, damp, tol, niter=args.niter))
    t0 = time.perf_counter()
    lowered = fn.lower(StackOp, StackedDistributedArray(
        [abstract(), abstract()]), abstract(), jnp.float32(0),
        jnp.float32(0))
    compiled = lowered.compile()
    print(f"compiled for {topo.devices[0].device_kind} in "
          f"{time.perf_counter() - t0:.1f}s; a volume is {vol} bytes")
    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)
    print("in volumes: arguments %.2f, outputs %.2f, aliased %.2f, "
          "temporaries %.2f" % tuple(
              b / vol for b in (ma.argument_size_in_bytes,
                                ma.output_size_in_bytes,
                                ma.alias_size_in_bytes,
                                ma.temp_size_in_bytes)))
    hlo = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(hlo)
    print("pmt_conv1d calls:", len(re.findall(
        r"custom_call_target=\"tpu_custom_call\"", hlo)))
    big = {}
    for line in hlo.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = f32\[([\d,]+)\]\S* "
                     r"(\w[\w\-]*)\(", line)
        if not m:
            continue
        size = 4 * int(np.prod([int(d) for d in m.group(2).split(",")]))
        kind = m.group(3)
        if size >= vol and kind not in ("fusion", "parameter",
                                        "get-tuple-element", "bitcast",
                                        "custom-call"):
            big[kind] = big.get(kind, 0) + 1
    print("volume-sized instructions that are no fusion, kernel or "
          "bitcast:", big)
    return 0


if __name__ == "__main__":
    sys.exit(main())
